"""Paper Tab. 1 analog — per-op cost of FPISA primitives vs native FP add.

The paper synthesizes switch ALUs at 15 nm (default ALU 505 um^2 / FPISA ALU
619 um^2 / hard FPU 3838 um^2). We cannot synthesize silicon; the analog is
the op-level cost of each FPISA primitive on the programmable substrate we
target: instruction/flop/byte counts from XLA cost analysis plus measured CPU
wall time per element. The headline ratio mirrors the paper's: FPISA ops cost
a small-integer multiple of a native add, versus the >5x area/power of a hard
FPU."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, scaled, timeit
from repro.core import fpisa as F

N = 1 << 20


def run():
    n = scaled(N, 1 << 14)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y = jnp.asarray(rng.standard_normal(n), jnp.float32)

    native_add = jax.jit(lambda a, b: a + b)
    fpisa_encode = jax.jit(lambda a: F.encode(a))
    fpisa_add = jax.jit(
        lambda a, b: F.fpisa_a_add(F.encode(a), F.encode(b))[0].man
    )
    fpisa_full = jax.jit(
        lambda a, b: F.fpisa_add_full(F.encode(a), F.encode(b))[0].man
    )
    fpisa_renorm = jax.jit(lambda a: F.renormalize(F.encode(a)))

    t_add, _ = timeit(native_add, x, y)
    rows = [
        ("tab1.native_fp_add", native_add, (x, y)),
        ("tab1.fpisa_encode", fpisa_encode, (x,)),
        ("tab1.fpisa_a_add", fpisa_add, (x, y)),
        ("tab1.fpisa_full_add", fpisa_full, (x, y)),
        ("tab1.fpisa_renormalize", fpisa_renorm, (x,)),
    ]
    for name, fn, args in rows:
        dt, _ = timeit(fn, *args)
        ca = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
        flops = ca.get("flops", 0)
        emit(name, dt * 1e6, f"x_native={dt/t_add:.2f};ops_per_elem={flops/n:.1f}")
    # paper's silicon numbers for context (um^2 at 15nm, Tab. 1)
    emit("tab1.paper_area_default_alu", 0, "um2=505.4")
    emit("tab1.paper_area_fpisa_alu", 0, "um2=618.6;ratio=1.22")
    emit("tab1.paper_area_alu_plus_fpu", 0, "um2=3837.7;ratio=7.59")
