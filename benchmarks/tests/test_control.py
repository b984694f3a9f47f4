"""The two proofs that ``correct`` can fail, at a size a test run can hold.

1. The control (the plain reference one precision down, in the program's
   place) comes out as not correct under the toy configurations' limits.
2. A run with the timed path broken underneath — a train step that returns
   its state unchanged, a served token altered where it is produced — comes
   out as not correct, the harness's look for a chip skipped (rehearsal).
3. Dropout runs at 2**-30 as the identity (the configurations' `reduced`):
   that this holds in the program's kernels, and that a real mask under the
   same call would come out as not correct.
"""

import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run as run_mod


def _run(argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_mod.main(argv)
    return rc, [json.loads(x) for x in buf.getvalue().splitlines() if x.startswith("{")]


@pytest.mark.parametrize("cell", ["bart-large-cnn.train", "t5-large.train", "bart-large-cnn.serve-steady"])
def test_the_control_is_not_correct(cell):
    rc, lines = _run(["--workload", cell, "--seed", "21", "--seconds", "1", "--trace", "0", "--rehearse", "--control"])
    assert all(x["ok"] for x in lines if "check" in x), [x for x in lines if "check" in x]
    controls = [x for x in lines if "control" in x]
    assert controls and any(x["caught"] for x in controls), controls


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from distributed_llms_example_tpu.train import trainer as trainer_mod

    build = trainer_mod.Trainer._build_train_step

    def broken_build(self):
        build(self)
        real = self.train_step

        def step(state, batch, *rng):
            kept = jax.tree.map(jnp.copy, state)  # the real step donates its input
            _, metrics = real(state, batch, *rng)
            return kept, metrics

        self.train_step = step

    monkeypatch.setattr(trainer_mod.Trainer, "_build_train_step", broken_build)
    rc, lines = _run(["--workload", "bart-large-cnn.train", "--seed", "22", "--seconds", "1", "--trace", "0", "--rehearse"])
    failed = {x["check"] for x in lines if "check" in x and not x["ok"]}
    assert "param_change_norm_gap_worst_leaf" in failed, lines[-6:]


def test_an_altered_served_token_is_not_correct(monkeypatch):
    from distributed_llms_example_tpu.serving import engine as engine_mod

    real = engine_mod.ServeSession._step_round

    def broken_round(self):
        before = [len(o) for o in self.outputs]
        finished = real(self)
        for rid, n in enumerate(before):
            if len(self.outputs[rid]) > n and len(self.outputs[rid]) % 5 == 0:
                self.outputs[rid][-1] = (self.outputs[rid][-1] + 7) % 200 + 3
        return finished

    monkeypatch.setattr(engine_mod.ServeSession, "_step_round", broken_round)
    rc, lines = _run(["--workload", "bart-large-cnn.serve-steady", "--seed", "23", "--seconds", "3", "--trace", "0", "--rehearse"])
    failed = {x["check"] for x in lines if "check" in x and not x["ok"]}
    assert failed, lines[-6:]


def test_a_real_dropout_mask_is_not_correct(monkeypatch):
    """The configurations run dropout at 2**-30, where the program keeps every
    element, and the reference takes it as the identity.  Were the program to
    apply a real mask under that call (here: the published 0.1), the reference
    no longer follows the step and ``correct`` comes out false."""
    from distributed_llms_example_tpu.ops import fused_dropout as fd

    real = fd.dropout

    def masking(x, key, rate, **kw):
        return real(x, key, 0.1 if rate > 0.0 else rate, **kw)

    monkeypatch.setattr(fd, "dropout", masking)
    rc, lines = _run(["--workload", "bart-large-cnn.train", "--seed", "24", "--seconds", "1", "--trace", "0", "--rehearse"])
    failed = {x["check"] for x in lines if "check" in x and not x["ok"]}
    assert "first_grad_rel_diff_median_leaf" in failed, lines[-6:]


def test_the_stated_dropout_rate_is_the_identity_in_the_programs_kernels(bench):
    """What `reduced` claims for the dropout keys rests on the program's own
    arithmetic: a 24-bit keep threshold that rounds to "keep everything" and a
    float32 1/(1-p) that rounds to 1.  A kernel that compares more bits, or a
    rate someone edits, fails here before it can run unfollowed on the chip."""
    import numpy as np

    from benchmarks.harness import spec as spec_mod
    from distributed_llms_example_tpu.ops import fused_dropout as fd

    for c in bench["configs"]:
        cfg = spec_mod.load_json(os.path.join(spec_mod.ROOT, c["file"]))
        rate = spec_mod.load_module("adapters", cfg["family"]).program_config_overrides(cfg)["dropout_rate"]
        assert 0.0 < rate < 1.0  # above 0, so the dropout kernels do run
        assert fd.keep_threshold(rate) == 1 << 24, c["name"]  # (bits >> 8) < 2**24 holds for every draw
        assert np.float32(1.0 / (1.0 - rate)) == np.float32(1.0), c["name"]
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.bfloat16)
    for residual in (None, x[::-1]):
        y = fd.fused_dropout(x, jnp.int32(7), rate, residual=residual, interpret=True)
        want = x if residual is None else (residual.astype(jnp.float32) + x.astype(jnp.float32)).astype(x.dtype)
        assert bool(jnp.all(y == want))
