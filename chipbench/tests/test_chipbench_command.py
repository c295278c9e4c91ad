"""The command refuses to run where there is no TPU: no result, a non-zero exit."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_module():
    spec = importlib.util.spec_from_file_location("chipbench_run", os.path.join(ROOT, "chipbench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_with_no_result_on_cpu(capsys):
    import jax

    assert jax.devices()[0].platform == "cpu"
    for cell in ("stablelm-3b.train.b1x4096", "qwen1.5-0.5b.dp4.b8x512"):
        rc = _run_module().main(["--workload", cell, "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"])
        out = capsys.readouterr()
        assert rc != 0
        assert out.out == ""
        assert "no TPU" in out.err
