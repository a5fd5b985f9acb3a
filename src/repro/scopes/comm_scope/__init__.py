"""Comm|Scope — CPU-GPU/NVLink communication → mesh collectives over ICI.

Two measurement modes, mirroring the SCOPE philosophy of measuring the
same axis at different abstraction levels:

  * measured — run the collective on whatever local device mesh exists
    (1 device here → intra-chip copy baseline; the multi-device path is
    exercised by tests/test_comm_scope_multidev.py in a subprocess with 8
    host devices);
  * modeled  — analytic v5e ICI cost for the production meshes
    (ring all-reduce 2(n-1)/n, all-gather (n-1)/n, all-to-all (n-1)/n²)
    so the numbers feeding §Roofline are explicit and testable; one
    typed family with a ``kind`` axis covers every collective.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ParamSpace, Scope, State, benchmark
from repro.core.registry import BenchmarkRegistry
from repro.core.sysinfo import TPU_V5E
from repro.launch.mesh import make_mesh

NAME = "comm"


def modeled_collective_seconds(kind: str, nbytes: int, axis_size: int,
                               link_bw: float = None) -> float:
    """Analytic ring-collective time on one ICI axis (v5e)."""
    bw = link_bw or TPU_V5E["ici_link_bandwidth"]
    n = axis_size
    if n <= 1:
        return 0.0
    factor = {"all_reduce": 2.0 * (n - 1) / n,
              "all_gather": (n - 1) / n,
              "reduce_scatter": (n - 1) / n,
              "all_to_all": (n - 1) / (n * n),
              "ppermute": 1.0}[kind]
    # bidirectional ring: 2 links usable per axis
    return factor * nbytes / (2 * bw)


def _register(registry: BenchmarkRegistry) -> None:
    def psum_setup(params):
        n = jax.device_count()
        elems = params.bytes // 4
        mesh = make_mesh((n,), ("x",))
        x = jnp.ones((n, elems), jnp.float32)

        @jax.jit
        def f(x):
            return jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                                 in_specs=jax.sharding.PartitionSpec("x"),
                                 out_specs=jax.sharding.PartitionSpec())(x)
        return f, x

    @benchmark(scope=NAME, registry=registry)
    def all_reduce_measured(state: State):
        """psum over the local device mesh (1 device → copy baseline);
        mesh + jit construction live in the fixture, the reduced array
        is the sync deliverable."""
        f, x = state.fixture
        while state.keep_running():
            state.deliver(f(x))
        state.set_bytes_processed(state.params.bytes)
        state.counters["devices"] = jax.device_count()
    all_reduce_measured.range_multiplier_args(1 << 16, 1 << 22, mult=8)
    all_reduce_measured.set_arg_names(["bytes"])
    all_reduce_measured.set_fixture(psum_setup)

    @benchmark(scope=NAME, registry=registry)
    def collective_modeled_v5e(state: State):
        """Analytic v5e ICI collective over one mesh axis — the ``kind``
        axis replaces four per-collective family clones (feeds the
        §Roofline collective term)."""
        p = state.params
        t = modeled_collective_seconds(p.kind, p.bytes, p.axis)
        state.set_iteration_time(t)
        while state.keep_running():
            state.set_iteration_time(t)
        state.counters["modeled_s"] = t
        state.counters["axis_size"] = p.axis
        state.set_bytes_processed(p.bytes)

    collective_modeled_v5e.param_space(
        kind=["all_reduce", "all_gather", "reduce_scatter", "all_to_all"],
        bytes=[1 << 20, 1 << 24, 1 << 28],
        axis=[16, 256])
    collective_modeled_v5e.manual_time().set_iterations(1)


SCOPE = Scope(name=NAME, version="2.0.0",
              description="Interconnect collectives: measured + v5e model",
              register=_register)
