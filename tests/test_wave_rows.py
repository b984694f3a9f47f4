"""An admission wave runs at one of two compiled row counts, chosen from the
queue: the mesh's batch shards where the chunk fits them, else
``prefill_batch``.  A row past the chunk was padding the others never saw,
so the count changes no served token; both sizes of every bucket are warm
before the first request, whatever the traffic then forms."""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.serving.engine import ServeConfig, ServingEngine

C = 4  # prefill_batch of every engine here: waves of 1..4 requests

# name -> (model, the mesh's data axis or None, ServeConfig fields beyond the common ones)
ENGINES = {
    "bart": ("bart-test", None, {}),
    "llama-flat": ("llama-test", None, {}),
    "llama-buckets": ("llama-test", None, {"prefill_buckets": (8,)}),
    "llama-paged": ("llama-test", None, {"paged_kv": True, "kv_block_size": 8}),
    "llama-prefix": ("llama-test", None, {"paged_kv": True, "kv_block_size": 8, "pool_blocks": 48,
                                          "prefix_cache": True, "prefix_cache_budget_gib": 0.25}),
    "lfm2": ("lfm2-moe-test", None, {}),
    "llama-drafter": ("llama-test", None, {"spec_tokens": 2, "spec_draft_model": "llama-test"}),
    "bart-data2": ("bart-test", 2, {}),
    "llama-data2": ("llama-test", 2, {}),
}
_built: dict = {}


def rig(name):
    """(engine, params, prompts), built once a name: the warm programs are the engine's."""
    if name not in _built:
        from distributed_llms_example_tpu.core.config import MeshConfig
        from distributed_llms_example_tpu.core.mesh import build_mesh
        from distributed_llms_example_tpu.parallel.sharding import shard_params

        model, data, kw = ENGINES[name]
        lm = load_model(model)
        # no end-of-sequence id: every request runs to its budget, so a wave's rows all stay to be compared
        config = dataclasses.replace(lm.config, eos_token_id=None)
        params = lm.init_params(0)
        mesh = None
        if data:
            mesh = build_mesh(MeshConfig(data=data), devices=jax.devices()[:data])
            params = shard_params(params, mesh)
        eng = ServingEngine(
            lm.module, config, mesh,
            ServeConfig(max_slots=C, prefill_batch=C, max_new_tokens=8, max_source_length=16,
                        log_every_steps=0, request_spans=False, **kw),
            is_seq2seq=lm.is_seq2seq,
        )
        rng = np.random.RandomState(11)
        shared = [int(t) for t in rng.randint(4, 120, 8)]  # one full block: the prefix cache's warm rows
        prompts = [shared + [int(t) for t in rng.randint(4, 120, rng.randint(1, 8))] for _ in range(C)]
        prompts[0] = prompts[0][:5]  # a prompt the short bucket holds, where there is one
        _built[name] = eng, params, prompts
    return _built[name]


def serve(eng, params, prompts):
    """Submit all at once (one wave), run dry; (outputs, waves by rows computed)."""
    sess = eng.open(params)
    for p in prompts:
        sess.submit(p)
    while sess.has_work():
        sess.step()
    sess.finalize()
    return list(sess.outputs), dict(sess._waves_by_rows)


@pytest.mark.parametrize("n", range(1, C + 1))
@pytest.mark.parametrize("name", list(ENGINES))
def test_a_wave_serves_the_same_tokens_at_either_row_count(name, n, monkeypatch, capsys):
    eng, params, prompts = rig(name)
    small = eng.wave_sizes[0]
    assert eng.wave_sizes == (ENGINES[name][1] or 1, C)
    got, by_rows = serve(eng, params, prompts[:n])
    assert all(len(o) == 8 for o in got)
    if name != "llama-prefix":  # there a wave is a cold chunk and a warm one, each at its own count
        assert by_rows == {small if n <= small else C: 1}
    else:
        assert set(by_rows) <= {small, C} and (n > 1 or by_rows == {small: 1})
    # as before this change: every chunk at prefill_batch rows
    monkeypatch.setattr(eng, "wave_rows", lambda _n: C)
    want, by_rows = serve(eng, params, prompts[:n])
    assert set(by_rows) == {C}
    assert got == want
    capsys.readouterr()


@pytest.mark.parametrize("name", list(ENGINES))
def test_both_sizes_of_every_bucket_are_warm_and_no_wave_retraces(name, capsys):
    eng, params, prompts = rig(name)
    eng.open(params).finalize()  # warm, if no test before did it
    pinned = dict(eng.trace_counts)
    programs = 2 * len(eng.buckets)
    assert pinned["prefill"] == pinned["admit"] == programs
    for extra in ("warm_admit", "draft_prefill", "draft_admit"):
        assert pinned.get(extra, programs) == programs
    seen: dict = {}
    for n in range(1, C + 1):
        _, by_rows = serve(eng, params, prompts[:n])
        seen.update(by_rows)
    assert set(seen) == set(eng.wave_sizes)
    assert eng.trace_counts == pinned
    capsys.readouterr()


def test_one_size_where_the_shards_are_the_cap(capsys):
    """prefill_batch == the mesh's batch shards: one program pair a bucket, as before."""
    lm = load_model("bart-test")
    eng = ServingEngine(
        lm.module, lm.config, None,
        ServeConfig(max_slots=2, prefill_batch=1, max_new_tokens=4, max_source_length=16, log_every_steps=0),
        is_seq2seq=True,
    )
    assert eng.wave_sizes == (1,) and eng.wave_rows(1) == 1
    eng.generate(lm.init_params(0), [[5, 6, 7], [8, 9]])
    assert eng.trace_counts == {"prefill": 1, "admit": 1, "decode_step": 1}
    capsys.readouterr()
