"""The five ``setup_*`` metrics: their arithmetic on a hand-built account, and
None (the metric left out of the line) where the program has none."""

import sys

import pytest

from benchmarks.harness import setup_account, spec as spec_mod

ACCOUNT = {
    "phases": {
        "trainer_init/model_init": {"s": 7.5, "trace_s": 0.5, "lower_s": 1.0, "compile_or_load_s": 5.0},
        "trainer_init/build_step": {"s": 0.25},
        "trainer_init": {"s": 12.0},
        "first_step": {"s": 160.0, "trace_s": 40.0, "lower_s": 100.0, "compile_or_load_s": 3.5},
    },
    "unattributed_s": {"trainer_init": 4.25, "first_step": 16.5},
    "programs": {"step_fn": {"n": 1, "trace_s": 40.0, "lower_s": 100.0, "compile_or_load_s": 3.5,
                             "cache_hits": 1, "cache_misses": 0, "cache_load_s": 3.0, "compile_saved_s": 190.0}},
    "others": {},
    "totals": {"trace_s": 40.5, "lower_s": 101.0, "compile_or_load_s": 8.5, "cache_hits": 70, "cache_misses": 2},
    "cache_dir": "/checkout/benchmarks/.cache/jax",
}

WANT = {"setup_program_s": 172.0, "setup_trace_s": 40.5, "setup_lower_s": 101.0,
        "setup_compile_or_load_s": 8.5, "setup_cache_misses": 2.0}


@pytest.fixture
def program_account(monkeypatch):
    """What the program's module says its account is (``snapshot``)."""
    from distributed_llms_example_tpu.obs import setup

    def give(account):
        monkeypatch.setattr(setup, "snapshot", lambda: account)

    return give


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_built_account(name, program_account):
    program_account(ACCOUNT)
    assert spec_mod.load_module("layer_metrics", name).read({"trace": None}) == WANT[name]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_none_before_ready(name, program_account):
    program_account(None)
    assert spec_mod.load_module("layer_metrics", name).read({}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_none_for_a_program_without_the_module(name, monkeypatch):
    """An older commit: ``obs/setup.py`` is not there, the import fails."""
    import distributed_llms_example_tpu.obs as obs_package

    monkeypatch.delattr(obs_package, "setup", raising=False)
    monkeypatch.setitem(sys.modules, "distributed_llms_example_tpu.obs.setup", None)
    with pytest.raises(ImportError):
        from distributed_llms_example_tpu.obs import setup  # noqa: F401
    assert setup_account.load() is None
    assert spec_mod.load_module("layer_metrics", name).read({}) is None


def test_the_entries_list_every_cell(bench):
    cells = [w["name"] for w in bench["workloads"]]
    mine = [m for m in bench["per_layer"] if m["name"] in WANT]
    assert [m["name"] for m in mine] == [
        "setup_program_s", "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s", "setup_cache_misses"]
    for m in mine:
        assert (m["layer"], m["moves"], m["better"], m["source"]) == ("set-up", "setup_s", "lower", "program_span")
        assert m["workloads"] == cells
