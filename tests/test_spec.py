"""Speculative multi-token decode (ISSUE 20): draft-then-verify.

Acceptance pins: speculative greedy output BIT-identical to plain greedy
through the engine — flat, paged+int8, warm prefix-cache multi-turn, and
the registry draft-model path — with ``accepted_tokens_per_step > 1.0``
when the drafter predicts; an adversarial-draft request storm leaves the
paged pool's free list byte-exact and the prefix-cache hash index free
of speculative entries; the router replica-kill leg stays bit-identical
with speculation on and aggregates the acceptance ledger; the new
``serve_window``/``serve_summary``/``router_summary`` fields round-trip
through ``obs.report``'s loader into the '## Speculative decode' section
and the strict ``--min-acceptance-rate`` gate (missing measurement is
never a pass); and repo_lint rule 17 fences acceptance math to
``serving/spec.py`` + ``serving/cache_pool.py``."""

from __future__ import annotations

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_example_tpu.models.registry import load_model
from distributed_llms_example_tpu.obs import sink as sink_mod
from distributed_llms_example_tpu.obs.chaos import parse_chaos
from distributed_llms_example_tpu.obs.report import build_report, render_markdown
from distributed_llms_example_tpu.serving import cache_pool
from distributed_llms_example_tpu.serving import spec as spec_mod
from distributed_llms_example_tpu.serving.engine import (
    ServeConfig,
    ServingEngine,
    trim_eos,
)
from distributed_llms_example_tpu.serving.router import (
    ReplicaRouter,
    RouterConfig,
)


@pytest.fixture(autouse=True)
def _default_sink():
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    yield
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))


# ------------------------------------------------------------ pure drafting


def test_ngram_draft_repetition_and_fallback():
    """The self-drafter is a longest-suffix n-gram lookup: on a repeating
    stream it proposes the continuation of the most recent earlier
    occurrence; with no repetition it falls back to repeating the last
    token; it always returns exactly k proposals."""
    # period-3 loop: the suffix trigram recurs, the draft continues it
    h = [5, 6, 7, 5, 6, 7, 5, 6]
    assert spec_mod.ngram_draft(h, 4) == [7, 5, 6, 7]
    # no repetition at all → last-token fallback
    assert spec_mod.ngram_draft([1, 2, 3], 3) == [3, 3, 3]
    assert spec_mod.ngram_draft([], 2) == [0, 0]
    # the match running off the end continues the periodic fill
    assert spec_mod.ngram_draft([9, 9], 4) == [9, 9, 9, 9]
    # most RECENT prior occurrence wins (suffix [2] matches twice; the
    # later match at index 3 is followed by 8, the earlier one by 7)
    assert spec_mod.ngram_draft([2, 7, 0, 2, 8, 1, 2], 1) == [8]
    for k in (1, 3, 7):
        assert len(spec_mod.ngram_draft([4, 5], k)) == k


def test_ngram_drafts_batched_pads_idle():
    out = spec_mod.ngram_drafts([[5, 6, 5], None, []], 3, pad=0)
    assert out.shape == (3, 3) and out.dtype == np.int32
    # unigram match at index 0: continuation [6, 5], then period-1 fill
    assert out[0].tolist() == [6, 5, 5]
    assert out[1].tolist() == [0, 0, 0]
    assert out[2].tolist() == [0, 0, 0]


def test_acceptance_lengths_rule_and_room_clamp():
    """The acceptance rule verbatim: cumprod of draft==target prefix
    matches, clamped to the slot's remaining budget room — the clamp
    truncates acceptance, it never changes which tokens match."""
    # x rows: [last, d1, d2, d3]; target rows: argmax at each position
    x = jnp.asarray([
        [10, 7, 8, 9],   # drafts all match → accept 3
        [10, 7, 8, 9],   # d1 matches, d2 wrong → accept 1
        [10, 5, 8, 9],   # d1 wrong (even though d2 'matches') → accept 0
        [10, 7, 8, 9],   # all match but room clamps at 2
    ], jnp.int32)
    target = jnp.asarray([
        [7, 8, 9, 1],
        [7, 2, 9, 1],
        [7, 8, 9, 1],
        [7, 8, 9, 1],
    ], jnp.int32)
    room = jnp.asarray([3, 3, 3, 2], jnp.int32)
    got = np.asarray(spec_mod.acceptance_lengths(x, target, room))
    assert got.tolist() == [3, 1, 0, 2]


# ------------------------------------------------------- engine bit-identity


def _requests(rng, n=8, lo=3, hi=14, vocab=120):
    return [list(rng.randint(4, vocab, rng.randint(lo, hi))) for _ in range(n)]


def _engine(lm, *, W=16, L=8, slots=2, **kw):
    return ServingEngine(
        lm.module, lm.config, None,
        ServeConfig(
            max_slots=slots, prefill_batch=slots, max_new_tokens=L,
            max_source_length=W, log_every_steps=0, request_spans=False, **kw,
        ),
        is_seq2seq=False,
    )


@pytest.fixture(scope="module")
def llama_spec():
    """One plain flat-f32 greedy run: the oracle every speculative
    configuration must reproduce bit-for-bit."""
    lm = load_model("llama-test")
    params = lm.init_params(0)
    rng = np.random.RandomState(7)
    reqs = _requests(rng)
    plain = _engine(lm).generate(params, reqs)
    return lm, params, reqs, plain


def test_engine_spec_flat_bit_identical_and_ledger(llama_spec):
    """THE acceptance pin: n-gram speculative decode on the flat cache
    emits plain greedy's exact tokens (slot reuse included — 8 requests
    over 2 slots), the ledger adds up (emitted == the tokens decoded,
    per-slot accepted_tokens_per_step >= 1 by construction), and a
    second session retraces nothing."""
    lm, params, reqs, plain = llama_spec
    eng = _engine(lm, spec_tokens=3)
    outs = eng.generate(params, reqs)
    assert outs == plain
    st = eng.last_stats
    # the first token of each output is prefill's; the rest are decode's
    assert st.spec_emitted == st.decode_tokens
    assert st.decode_tokens == sum(len(o) for o in outs) - len(reqs)
    assert st.spec_steps > 0 and st.spec_slot_rounds >= st.spec_steps
    assert st.spec_drafted == 3 * st.spec_slot_rounds
    # every emitted token beyond one-per-slot-round is an accepted draft
    assert st.spec_emitted >= st.spec_slot_rounds
    assert 0 <= st.spec_accepted <= st.spec_drafted
    traces = dict(eng.trace_counts)
    assert traces["spec_verify"] == 1
    assert eng.generate(params, reqs) == plain
    assert eng.trace_counts == traces  # zero-recompile churn


def test_engine_spec_paged_int8_bit_identical(llama_spec):
    """Composition: speculation over the paged pool with int8 KV matches
    the NON-speculative paged int8 engine token-for-token (same kernel
    path, same dequant — the argmax expression never forks), and the
    pool drains to zero."""
    lm, params, reqs, _ = llama_spec
    kw = dict(paged_kv=True, kv_block_size=8, kv_cache_dtype="int8")
    want = _engine(lm, **kw).generate(params, reqs)
    eng = _engine(lm, spec_tokens=3, **kw)
    assert eng.generate(params, reqs) == want
    assert eng.pool.blocks_in_use == 0


def test_engine_spec_warm_prefix_multi_turn_bit_identical(llama_spec):
    """Speculation composes with warm prefix-cache hits: shared-prefix
    multi-turn traffic through spec + prefix-cache reproduces the plain
    flat engine's tokens, still HITS the cache, and the hash index holds
    only prompt-chain hashes — never a speculative block."""
    lm, params, _, _ = llama_spec
    rng = np.random.RandomState(23)
    sys_toks = [int(t) for t in rng.randint(4, 120, 8)]
    reqs = [
        sys_toks + [int(t) for t in rng.randint(4, 120, rng.randint(2, 8))]
        for _ in range(8)
    ]
    plain = _engine(lm).generate(params, reqs)
    eng = _engine(
        lm, spec_tokens=3,
        paged_kv=True, kv_block_size=8, pool_blocks=24,
        prefix_cache=True, prefix_cache_budget_gib=0.25,
    )
    outs = eng.generate(params, reqs)
    assert outs == plain
    st = eng.last_stats
    assert st.prefix_hits == len(reqs) - 1  # the shared system block
    assert eng.pool.blocks_in_use == 0
    prompt_hashes = set()
    for r in reqs:
        prompt_hashes.update(cache_pool.chain_hashes(r[:16], 8))
    assert set(eng.pool._index) <= prompt_hashes


def test_engine_spec_draft_model_bit_identical_and_multi_token(llama_spec):
    """The registry draft-model path: with the draft sharing the
    target's weights its proposals ARE the target argmax, so acceptance
    is near-total and the per-slot multi-token rate clears 1.0 by a wide
    margin — while output stays bit-identical to plain greedy (the rule
    accepts nothing greedy would not have emitted)."""
    lm, params, _, _ = llama_spec
    rng = np.random.RandomState(11)
    reqs = _requests(rng, n=6)
    L = 16  # long budgets: room-clamps would mask the acceptance signal
    plain = _engine(lm, L=L).generate(params, reqs)
    eng = _engine(
        lm, L=L, spec_tokens=3, spec_draft_model="llama-test",
        paged_kv=True, kv_block_size=8,
    )
    outs = eng.generate(params, reqs)
    assert outs == plain
    st = eng.last_stats
    atps = st.spec_emitted / max(st.spec_slot_rounds, 1)
    assert atps > 1.0
    assert st.spec_accepted / max(st.spec_drafted, 1) > 0.5
    assert eng.pool.blocks_in_use == 0


def test_engine_spec_validates_composition():
    """Config fencing: seq2seq targets, out-of-range k, and seq2seq
    draft models are rejected at construction — not at decode time."""
    t5 = load_model("t5-test", load_weights=False)
    with pytest.raises(ValueError, match="causal decode"):
        ServingEngine(
            t5.module, t5.config, None,
            ServeConfig(max_slots=2, prefill_batch=2, spec_tokens=2),
            is_seq2seq=True,
        )
    lm = load_model("llama-test", load_weights=False)
    with pytest.raises(ValueError, match="spec_tokens=8"):
        _engine(lm, spec_tokens=8)
    with pytest.raises(ValueError, match="seq2seq"):
        _engine(lm, spec_tokens=2, spec_draft_model="t5-test")


# ------------------------------------------------------- rollback hygiene


def test_spec_pool_storm_adversarial_drafts_no_leak(llama_spec, monkeypatch):
    """The rollback pin: a request storm whose drafts are FORCED wrong
    (adversarial n-gram monkeypatch → every round rejects) leaves the
    paged pool byte-exact — every block back on the free list, refcount
    invariants clean, and not one speculative entry in the prefix-cache
    hash index — while output still matches plain greedy (a wrong draft
    costs throughput, never correctness)."""
    lm, params, _, plain_unused = llama_spec
    rng = np.random.RandomState(31)
    reqs = _requests(rng, n=12)
    plain = _engine(lm).generate(params, reqs)

    def adversarial(histories, k, pad):
        # propose tokens the target essentially never argmaxes (id 3 is
        # outside the 4..120 prompt range) — rejection every round
        return np.full((len(histories), k), 3, np.int32)

    monkeypatch.setattr(spec_mod, "ngram_drafts", adversarial)
    eng = _engine(
        lm, spec_tokens=3,
        paged_kv=True, kv_block_size=8, pool_blocks=24,
        prefix_cache=True, prefix_cache_budget_gib=0.25,
    )
    pre_total = eng.pool.blocks_free
    outs = eng.generate(params, reqs)
    assert outs == plain
    st = eng.last_stats
    assert st.spec_accepted == 0  # the storm really was all-reject
    assert st.spec_emitted == st.spec_slot_rounds  # 1 bonus token/round
    assert eng.pool.blocks_in_use == 0
    # blocks_free counts warm blocks (reclaimable on demand): full
    # capacity is back, byte-exact to the pre-storm free list
    assert eng.pool.blocks_free == pre_total
    assert eng.pool.ref_invariant_violations([]) == []
    prompt_hashes = set()
    for r in reqs:
        prompt_hashes.update(cache_pool.chain_hashes(r[:16], 8))
    assert set(eng.pool._index) <= prompt_hashes


# ------------------------------------------------------- router + report


def test_router_replica_kill_spec_bit_identical(llama_spec):
    """Degraded-mode leg: replica_crash mid-run over spec-enabled
    replicas — every request completes bit-identical to the plain
    single-engine oracle, and the router summary aggregates the tier's
    acceptance ledger."""
    lm, params, _, _ = llama_spec
    rng = np.random.RandomState(41)
    reqs = _requests(rng, n=10, lo=3, hi=10)
    oracle = _engine(lm).generate(params, reqs)

    def spec_engine():
        return _engine(
            lm, spec_tokens=3,
            paged_kv=True, kv_block_size=8, pool_blocks=24,
        )

    router = ReplicaRouter(
        [spec_engine(), spec_engine()], params,
        RouterConfig(log_every_ticks=0, chaos=parse_chaos("replica_crash@4")),
    )
    outs = router.serve(reqs)
    eos, pad = lm.config.eos_token_id, lm.config.pad_token_id
    for got, want in zip(outs, oracle):
        assert trim_eos(got, eos, pad) == trim_eos(want, eos, pad)
    summary = router.last_stats
    assert summary["completed"] == len(reqs) and summary["shed"] == 0
    assert summary["spec_tokens"] == 3
    assert summary["spec_drafted_tokens"] > 0
    assert 0.0 <= summary["acceptance_rate"] <= 1.0
    assert summary["accepted_tokens_per_step"] >= 1.0


def test_spec_report_section_and_gate(llama_spec, tmp_path, capsys):
    """Schema round-trip + the gate cutting both ways: a spec-enabled
    run's serve_window/serve_summary fields load through the report into
    the '## Speculative decode' section; --min-acceptance-rate passes a
    floor the measured rate meets, fails one above it, and fails
    OUTRIGHT on a run with no spec measurement."""
    from distributed_llms_example_tpu.obs.report import main as report_main

    lm, params, _, _ = llama_spec
    rng = np.random.RandomState(43)
    reqs = _requests(rng, n=6)
    eng = ServingEngine(
        lm.module, lm.config, None,
        ServeConfig(
            max_slots=2, prefill_batch=2, max_new_tokens=16,
            max_source_length=16, log_every_steps=2, request_spans=False,
            spec_tokens=3, spec_draft_model="llama-test",
        ),
        is_seq2seq=False,
    )
    out = tmp_path / "run"
    sink_mod.install_sink(sink_mod.build_sink("jsonl", str(out)))
    eng.generate(params, reqs)
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    report = build_report(str(out))
    sp = report["spec"]
    assert sp is not None and sp["scope"] == "engine"
    st = eng.last_stats
    assert sp["acceptance_rate"] == pytest.approx(
        st.spec_accepted / max(st.spec_drafted, 1), abs=1e-4
    )
    assert sp["accepted_tokens_per_step"] == pytest.approx(
        st.spec_emitted / max(st.spec_slot_rounds, 1), abs=1e-4
    )
    assert sp["drafted_tokens"] == st.spec_drafted
    assert sp["spec_tokens"] == 3 and sp["draft_model"] == "llama-test"
    assert sp["windows"] > 0  # serve_window rows carried the new fields
    md = render_markdown(report)
    assert "## Speculative decode" in md
    assert "accepted tokens per step" in md
    capsys.readouterr()
    rate = sp["acceptance_rate"]
    assert report_main([
        str(out), "--strict", "--json",
        "--min-acceptance-rate", str(max(rate - 0.01, 1e-6)),
    ]) == 0
    assert report_main([
        str(out), "--strict", "--json",
        "--min-acceptance-rate", str(rate + 0.01),
    ]) == 1
    # a floor of 0 leaves the dispatch gate off: a serving run holds no
    # step_budget record and must not fail for it
    assert report_main([
        str(out), "--strict", "--json", "--min-dispatch-efficiency", "0",
        "--min-acceptance-rate", str(max(rate - 0.01, 1e-6)),
    ]) == 0
    # a run with NO spec-enabled summary: missing measurement = fail
    cold = tmp_path / "cold"
    sink_mod.install_sink(sink_mod.build_sink("jsonl", str(cold)))
    _engine(lm).generate(params, reqs[:2])
    sink_mod.install_sink(sink_mod.build_sink("stdout", ""))
    assert build_report(str(cold))["spec"] is None
    assert report_main([
        str(cold), "--strict", "--json", "--min-acceptance-rate", "0.1",
    ]) == 1
    capsys.readouterr()


# ------------------------------------------------------------------ lint


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), "..", "scripts", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_lint_rule17_fences_acceptance_math(tmp_path):
    """Rule 17: draft-vs-target compares and acceptance cumprods outside
    serving/spec.py + serving/cache_pool.py are violations; the owner
    files stay exempt."""
    repo_lint = _load_script("repo_lint")
    bad = tmp_path / "sneaky.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "def accept(draft_toks, target_toks):\n"
        "    hits = draft_toks == target_toks\n"
        "    return jnp.cumprod(hits, axis=1).sum(axis=1)\n"
    )
    rel = "distributed_llms_example_tpu/serving/sneaky.py"
    out = repo_lint.lint_file(str(bad), rel)
    assert len(out) == 2  # the compare AND the cumprod
    assert all("rule 17" in v or "spec" in v.lower() for v in out)
    # the same text is legal in the owning module
    assert repo_lint.lint_file(
        str(bad), "distributed_llms_example_tpu/serving/spec.py"
    ) == []
    # ...and outside serving/ the rule does not apply
    assert repo_lint.lint_file(
        str(bad), "distributed_llms_example_tpu/ops/sneaky.py"
    ) == []


def test_chatbot_requests_budgets_seed_stable():
    """with_budgets=True rides the SAME rng draws: requests and keys are
    bit-identical to the 2-tuple form, and each budget is the scripted
    reply length for that turn."""
    from distributed_llms_example_tpu.serving.loadgen import chatbot_requests

    kw = dict(sessions=3, turns=2, seed=5, reply_len=(2, 6))
    reqs, keys = chatbot_requests(**kw)
    reqs3, keys3, budgets = chatbot_requests(**kw, with_budgets=True)
    assert reqs3 == reqs and keys3 == keys
    assert len(budgets) == len(reqs)
    assert all(2 <= b <= 6 for b in budgets)
    # the budget IS the gap between a session's consecutive prompts
    # minus the next user message — spot-check via regeneration
    again = chatbot_requests(**kw, with_budgets=True)
    assert again == (reqs3, keys3, budgets)
