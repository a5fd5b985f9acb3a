"""MXU|Scope — the TCU|Scope analogue (paper Table IV: "Nvidia GPU tensor
cores" → TPU MXU systolic array).

One ``matmul`` family benchmarks the matrix unit across typed axes —
``backend`` (xla: jnp.dot as XLA emits it, the production path; pallas:
our explicitly-tiled kernel, interpret mode on CPU, native on TPU),
``dtype`` (f32, bf16 — the MXU-native dtype) and size ``n`` — instead
of the three hand-copied per-variant families this scope used to carry.
The fixture allocates operands and builds the jitted callable untimed;
the runner's warm phase measures the first call (trace + XLA compile)
as ``compile_time_s``, so the steady-state numbers never include
compilation.  Reports achieved FLOP/s plus, on a chip with published
peaks (repro.core.sysinfo.DEVICE_PEAKS), the least time the chip's bf16
peak allows; the CPU gets no such counter.
"""
import jax
import jax.numpy as jnp

from repro.core import ParamSpace, Scope, State, benchmark
from repro.core.registry import BenchmarkRegistry
from repro.core.sysinfo import device_peaks

NAME = "mxu"

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _register(registry: BenchmarkRegistry) -> None:
    def setup(params):
        n = params.n
        dtype = _DTYPES[params.dtype]
        if params.backend == "xla":
            fn = jax.jit(jnp.dot)
        else:
            from repro.kernels.matmul import matmul as pallas_matmul
            # block sizes come from the tuned defaults
            # (repro.kernels.tuning: tuned.json, env, or builtin)
            fn = lambda x, y: pallas_matmul(x, y)  # noqa: E731
        x = jnp.ones((n, n), dtype)
        y = jnp.ones((n, n), dtype)
        return fn, x, y

    @benchmark(scope=NAME, registry=registry)
    def matmul(state: State):
        """Square matmul through the selected backend/dtype.  The pallas
        rows are interpret-mode on CPU (correctness timing, not TPU
        performance — the BlockSpec tiling is the artifact).  The body
        delivers its product instead of blocking every iteration: the
        wall meter fences the whole pipelined batch once, before the
        clock stops."""
        fn, x, y = state.fixture
        while state.keep_running():
            state.deliver(fn(x, y))
        n = state.params.n
        flops = 2.0 * n * n * n
        state.counters["flops_per_call"] = flops
        peaks = device_peaks(next(iter(x.devices())))
        if peaks is not None:
            state.counters["model_roofline_s"] = \
                flops / peaks["peak_bf16_flops"]
        state.set_items_processed(int(flops))

    # pallas stays a single f32/256 point (interpret mode is slow on CPU);
    # the xla path sweeps the full dtype × size grid
    matmul.param_space(
        ParamSpace.product(backend=["xla", "pallas"],
                           dtype=["f32", "bf16"],
                           n=[256, 512, 1024])
        .where(lambda p: p.backend == "xla"
               or (p.dtype == "f32" and p.n == 256)))
    matmul.set_fixture(setup)
    # `python -m repro tune mxu/matmul` searches the Pallas block space
    # on the pallas instance and ships the winner as the kernel default
    matmul.set_tunable("matmul", bm=[64, 128, 256], bn=[64, 128, 256],
                       bk=[64, 128, 256],
                       instance={"backend": "pallas"})


SCOPE = Scope(name=NAME, version="2.0.0",
              description="MXU/tensor-core matmul characterization",
              register=_register)
