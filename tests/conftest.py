import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess with N host devices.

    Tests in THIS process must see exactly 1 device (per the project brief),
    so multi-device integration tests go through here.
    """
    env = dict(os.environ)
    # the child never touches an accelerator: the parent may hold it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={res.returncode})\nstdout:\n{res.stdout}\nstderr:\n{res.stderr[-4000:]}"
        )
    return res.stdout


@pytest.fixture(scope="session")
def multi_device_runner():
    return run_with_devices
