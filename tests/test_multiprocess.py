"""REAL multi-process integration tests (2 procs × 4 devices and the
reference's 4-machine shape, 4 procs × 2 devices).

The reference's distinguishing variant is genuinely multi-machine
(reference train-task.py:404-430: one process per host, NCCL rendezvous
over ``tcp://master:1234``).  Every other test in this suite simulates
multi-host on a single process with 8 virtual devices; these tests spawn
TWO OS processes that rendezvous through ``jax.distributed.initialize``
(gloo collectives over localhost) and run the full Trainer CLI end-to-end,
executing every ``process_count > 1`` branch that is otherwise dead code:

- ``initialize_distributed`` from the VH_* env triple (core/mesh.py)
- ``put_batch``'s ``make_array_from_process_local_data`` (train/step.py)
- the per-epoch bucket-width allgather (data/batching.py)
- cross-host eval row gathering + metric aggregation (evaluation/)
- the cadenced preemption agreement allgather (train/trainer.py)

Loss parity with a single-process 8-device run of the identical config is
the correctness oracle: same global batches, same mesh, same shardings —
the distribution mechanism must be invisible in the math.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = "distributed_llms_example_tpu.launch.cli"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(n_local_devices: int, *, rank: int | None = None,
               world: int | None = None, port: int | None = None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_local_devices}"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # never inherit rendezvous facts from an outer context
    for k in ("VH_MASTER_IP", "VH_WORLD_SIZE", "VH_RANK", "MASTER_ADDR", "WORLD_SIZE", "RANK"):
        env.pop(k, None)
    if rank is not None:
        env["VH_MASTER_IP"] = f"127.0.0.1:{port}"
        env["VH_WORLD_SIZE"] = str(world)
        env["VH_RANK"] = str(rank)
    return env


def _cli_args(outdir: str, train: str, val: str, **over) -> list[str]:
    opts = {
        "model-ckpt": "t5-test",
        "output-dir": outdir,
        "batch-size": 8,
        "num-epochs": 2,
        "train-file": train,
        "val-file": val,
        "mesh": "data=2,fsdp=2,tensor=2",
        "compute-dtype": "float32",  # exact loss parity across process layouts
        "log-every-steps": 1,
        "num-beams": 1,
        "eval-max-new-tokens": 8,
    }
    opts.update(over)
    args = [sys.executable, "-m", CLI]
    for k, v in opts.items():
        args += [f"--{k}", str(v)]
    return args


def _write_dataset(tmp_path) -> tuple[str, str]:
    recs = [
        {
            "dialogue": f"Speaker A: point {i} about the {i % 7} plan. "
                        f"Speaker B: noted, we will revisit item {i} tomorrow.",
            "summary": f"They discuss point {i} and defer it.",
        }
        for i in range(48)
    ]
    train, val = str(tmp_path / "train.json"), str(tmp_path / "val.json")
    with open(train, "w") as f:
        json.dump(recs[:40], f)
    with open(val, "w") as f:
        json.dump(recs[40:], f)
    return train, val


def _events(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


def _step_losses(events: list[dict]) -> dict[int, float]:
    return {e["step"]: e["loss"] for e in events if "step" in e and "loss" in e}


@pytest.fixture(scope="module")
def single_reference(tmp_path_factory):
    """One single-process 8-device run shared by every world-size variant:
    the correctness oracle all multi-process layouts must reproduce."""
    base = tmp_path_factory.mktemp("mp_ref")
    train, val = _write_dataset(base)
    single = subprocess.run(
        _cli_args(str(base / "single"), train, val),
        env=_child_env(8), cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert single.returncode == 0, single.stderr[-3000:]
    ev_single = _events(single.stdout)
    losses_single = _step_losses(ev_single)
    assert len(losses_single) == 10  # 40 examples / batch 8 × 2 epochs
    return train, val, ev_single, losses_single


@pytest.mark.slow
@pytest.mark.parametrize("world", [2, 4])
def test_multiprocess_loss_parity(tmp_path, single_reference, world):
    """``world`` procs × 8/world devices must reproduce the single-process
    8-device run bit-for-bit in batches and to float tolerance in
    losses/ROUGE.  world=4 is the reference's flagship 4-machine shape
    (reference valohai.yaml:82-87) and exercises rank>1 metric
    aggregation plus non-trivial by-start host-row ordering in the eval
    gather (evaluation/evaluate.py)."""
    train, val, ev_single, losses_single = single_reference

    port = _free_port()
    # stderr to FILES, not pipes: communicate() drains ranks sequentially,
    # and an undrained 64 KB stderr pipe (gloo/XLA chatter) on a waiting
    # rank would block it mid-write and deadlock a collective — the same
    # hazard the preemption test documents, ×world writers here
    errs = [open(str(tmp_path / f"err{r}.log"), "w") for r in range(world)]
    procs = [
        subprocess.Popen(
            # one SHARED output dir for all ranks: orbax's multi-process
            # save coordinates through the filesystem (every rank commits
            # its shards under the same checkpoint dir); per-rank dirs
            # deadlock its finalize barrier
            _cli_args(str(tmp_path / "multi"), train, val),
            env=_child_env(8 // world, rank=r, world=world, port=port),
            cwd=REPO, stdout=subprocess.PIPE, stderr=errs[r], text=True,
        )
        for r in range(world)
    ]
    outs = []
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=600)
        outs.append((p.returncode, out))
    for f in errs:
        f.close()
    assert all(rc == 0 for rc, _ in outs), "\n".join(
        open(str(tmp_path / f"err{r}.log")).read()[-2000:] for r in range(world)
    )

    ev0 = _events(outs[0][1])
    report = next(e for e in ev0 if e.get("event") == "device_report")
    assert report["process_count"] == world and report["global_device_count"] == 8
    losses_multi = _step_losses(ev0)
    assert sorted(losses_multi) == sorted(losses_single)
    for s, loss in losses_single.items():
        assert losses_multi[s] == pytest.approx(loss, rel=2e-4), (
            f"step {s}: single={loss} multi={losses_multi[s]}"
        )
    # eval ran the cross-host row-gather path and agreed on scores
    eval_single = [e for e in ev_single if e.get("event") == "eval"][-1]
    eval_multi = [e for e in ev0 if e.get("event") == "eval"][-1]
    for k in ("rouge1", "rougeL"):
        assert eval_multi[k] == pytest.approx(eval_single[k], abs=1e-6)
    # metrics logging is process-0-only: ranks 1+ must not emit step lines
    for rc, out in outs[1:]:
        assert not _step_losses(_events(out))
    # the final artifact is an HF checkpoint written collaboratively into
    # the shared dir (params gathered across hosts, process 0 writes)
    model_dir = tmp_path / "multi" / "model"
    assert (model_dir / "model.safetensors").is_file()
    assert (model_dir / "config.json").is_file()


@pytest.mark.slow
def test_two_process_preemption_and_resume(tmp_path):
    """SIGTERM on ONE rank must stop BOTH at an agreed step (the cadenced
    allgather), checkpoint, exit cleanly — then a resumed run finishes.

    Bounded retry (4 attempts, fresh dirs/ports each), TARGETED: this
    leg is ENVIRONMENT-flaky — on this container the gloo/coordination
    layer dies in the rendezvous preamble ("op.preamble.length <=
    op.nbytes"), with a mid-run "Connection closed by peer", or with the
    coordination-service heartbeat timeout, at roughly every other
    rendezvous (each cycle runs TWO: initial + resume), verified
    identical at clean pre-change HEAD in a worktree.  Only failures
    matching those infra signatures retry; anything else — a real
    product regression — fails on the FIRST attempt."""
    last: Exception | None = None
    for attempt in range(4):
        root = tmp_path / f"attempt{attempt}"
        root.mkdir()
        # pytest.fail raises Failed, a BaseException subclass Exception
        # does NOT cover — name it explicitly so a deadline fail inside
        # the cycle reaches the signature check instead of skipping it
        try:
            _preemption_and_resume_cycle(root)
            return
        except (Exception, pytest.fail.Exception) as e:
            text = str(e)
            if not any(sig in text for sig in _INFRA_FLAKE_SIGNATURES):
                raise
            last = e
    assert last is not None
    raise last


# the gloo/coordination-service failure modes this container produces on
# an otherwise-green run (see test docstring) — the ONLY failures the
# bounded retry above absorbs
_INFRA_FLAKE_SIGNATURES = (
    "op.preamble",
    "Connection closed by peer",
    "heartbeat timeout",
    "coordination service",
    "CoordinationService",
)


def _preemption_and_resume_cycle(tmp_path):
    train, val = _write_dataset(tmp_path)
    outdir = str(tmp_path / "out")  # shared by both ranks (see above)
    port = _free_port()

    # stderr goes to files: the test reads stdout incrementally, and a
    # PIPE'd stderr nobody drains (gloo/XLA chatter) could fill and block
    # the children
    errs = [open(str(tmp_path / f"err{r}.log"), "w") for r in range(2)]

    def launch(r: int, port_: int, **over) -> subprocess.Popen:
        return subprocess.Popen(
            _cli_args(outdir, train, val, **{"evaluation-steps": 0, **over}),
            env=_child_env(4, rank=r, world=2, port=port_),
            cwd=REPO, stdout=subprocess.PIPE, stderr=errs[r], text=True,
        )

    procs = [launch(r, port, **{"num-epochs": 40}) for r in range(2)]
    # wait until rank 0 has taken a few steps, then SIGTERM rank 0 ONLY
    buf = []
    deadline = time.time() + 420
    while time.time() < deadline:
        line = procs[0].stdout.readline()
        if not line:
            break
        buf.append(line)
        if '"step": 3' in line:
            procs[0].send_signal(signal.SIGTERM)
            break
    else:
        pytest.fail("rank 0 never reached step 3")

    rest0, _ = procs[0].communicate(timeout=420)
    out1, _ = procs[1].communicate(timeout=420)
    for f in errs:
        f.close()
    for r, p in enumerate(procs):
        assert p.returncode == 0, open(str(tmp_path / f"err{r}.log")).read()[-3000:]
    ev0 = _events("".join(buf) + rest0)
    pre = [e for e in ev0 if e.get("event") == "preempted"]
    assert pre, "rank 0 did not checkpoint-and-exit on SIGTERM"
    stopped_at = pre[0]["step"]
    assert stopped_at >= 3
    # the agreed-step checkpoint committed (tmp suffix gone = every rank's
    # shards landed and the finalize barrier passed)
    assert os.path.isdir(os.path.join(outdir, "checkpoints", str(stopped_at)))

    # resume: fresh pair, same output dirs, larger epoch budget than the
    # preempted step so the run both resumes and finishes
    port2 = _free_port()
    errs = [open(str(tmp_path / f"err2_{r}.log"), "w") for r in range(2)]
    procs = [launch(r, port2, **{"num-epochs": 4}) for r in range(2)]
    outs = [p.communicate(timeout=600) for p in procs]
    for f in errs:
        f.close()
    assert all(p.returncode == 0 for p in procs), "\n".join(
        open(str(tmp_path / f"err2_{r}.log")).read()[-2000:] for r in range(2)
    )
    ev = _events(outs[0][0])
    assert any(e.get("event") == "resumed" and e["step"] == stopped_at for e in ev)
    assert any(e.get("event") == "done" for e in ev)
