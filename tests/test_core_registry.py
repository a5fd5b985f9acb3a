"""Core benchmark registry: registration, sweeps, filtering (paper §III)."""
import pytest
from hypothesis_compat import given, settings, st

from repro.core.benchmark import Benchmark, State
from repro.core.registry import BenchmarkRegistry, benchmark


def make_registry():
    return BenchmarkRegistry()


def test_register_and_filter():
    reg = make_registry()

    @benchmark(scope="s1", registry=reg)
    def foo(state):
        pass

    @benchmark(scope="s2", registry=reg)
    def bar(state):
        pass

    assert len(reg) == 2
    assert [b.name for b in reg.filter("foo")] == ["s1/foo"]
    assert [b.name for b in reg.filter(".*", scopes=["s2"])] == ["s2/bar"]
    assert reg.filter("nomatch") == []


def test_duplicate_rejected():
    reg = make_registry()

    @benchmark(scope="s", registry=reg)
    def foo(state):
        pass

    with pytest.raises(ValueError):
        benchmark(name="foo", scope="s", registry=reg)(lambda s: None)


def test_instance_names_args():
    b = Benchmark("s/b", lambda s: None)
    b.args([1, 2]).args([3, 4]).set_arg_names(["x", "y"])
    names = [n for n, _ in b.instances()]
    assert names == ["s/b/x:1/y:2", "s/b/x:3/y:4"]


def test_range_multiplier():
    b = Benchmark("s/b", lambda s: None).range_multiplier_args(8, 64, mult=2)
    assert [a[0] for a in b.arg_sets] == [8, 16, 32, 64]


def test_remove_scope():
    reg = make_registry()
    benchmark(scope="a", registry=reg)(lambda state: None)
    reg.remove_scope("a")
    assert len(reg) == 0


@given(st.lists(st.lists(st.integers(1, 8), min_size=1, max_size=3,
                         unique=True),     # duplicate arg-sets are refused
                min_size=1, max_size=3))
@settings(max_examples=25, deadline=None)
def test_args_product_cardinality(lists):
    b = Benchmark("s/b", lambda s: None).args_product(lists)
    expect = 1
    for l in lists:
        expect *= len(l)
    assert len(b.arg_sets) == expect
    # every combo unique positions match input lists
    for combo in b.arg_sets:
        for i, v in enumerate(combo):
            assert v in lists[i]


def test_state_iteration_protocol():
    st_ = State(ranges=(5,), max_iterations=7)
    n = 0
    while st_.keep_running():
        n += 1
    assert n == 7 and st_.iterations == 7
    assert st_.range(0) == 5
    assert st_.elapsed > 0


def test_state_skip_with_error():
    st_ = State(max_iterations=100)
    st_.skip_with_error("boom")
    assert not st_.keep_running()
    assert st_.error_occurred
