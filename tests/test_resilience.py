"""Fault-tolerance runtime tests (repro.resilience): the in-jit health
gate, the host verdict classifier, atomic resumable checkpoints, the
rollback-and-retry loop and its one-batch lookahead (batch k+1 drawn
while step k runs), and the deterministic fault harness — up to
the two acceptance properties: crash-at-step-k + resume reproduces an
uninterrupted run's losses bit-exactly, and an injected NaN-grad step
is detected, rolled back, and training re-converges."""
import argparse
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt
from repro.checkpoint.checkpoint import CheckpointError
from repro.optim import optimizer as opt
from repro.resilience import (ABORT, BUNDLE_KEYS, OK, ROLLBACK, SKIP,
                              CheckpointManager, CrashInjected,
                              CursorStream, EventLog, Fault,
                              FaultInjector, FaultPlan, HealthMonitor,
                              MonitorConfig, ResilientTrainer,
                              RetryPolicy, TrainingAborted, bundle_dict,
                              corrupt_shard, default_controls,
                              init_health, make_resilient_train_step)


# ---------------------------------------------------------------------------
# A tiny deterministic regression problem: fast, converges, bit-exact
# ---------------------------------------------------------------------------

_W_TRUE = np.random.default_rng(7).normal(size=(4, 1)).astype(np.float32)


def _loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _batches():
    rng = np.random.default_rng(42)
    while True:
        x = rng.normal(size=(8, 4)).astype(np.float32)
        yield {"x": jnp.asarray(x), "y": jnp.asarray(x @ _W_TRUE)}


def _fresh(lr=3e-2):
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    ocfg = opt.AdamWConfig(lr=lr, warmup_steps=0, schedule="constant",
                           weight_decay=0.0)
    state = opt.init(ocfg, params)
    step_fn = jax.jit(make_resilient_train_step(_loss_fn, ocfg),
                      donate_argnums=(0, 1, 2))
    return params, state, step_fn


def _trainer(tmp=None, *, faults=(), monitor=None, ckpt_every=0,
             resume=False, policy=None, on_device_loss=None):
    params, state, step_fn = _fresh()
    return ResilientTrainer(
        step_fn, params, state, CursorStream(_batches),
        monitor=monitor,
        manager=CheckpointManager(str(tmp)) if tmp is not None else None,
        injector=FaultInjector(FaultPlan.make(list(faults))),
        ckpt_every=ckpt_every, resume=resume, policy=policy,
        on_device_loss=on_device_loss)


# ---------------------------------------------------------------------------
# Guarded step: the fused bundle + the in-jit gate
# ---------------------------------------------------------------------------

def test_guarded_step_ok_path_trains():
    params, state, step_fn = _fresh()
    health = init_health()
    it = iter(_batches())
    first = last = None
    for _ in range(25):
        params, state, health, bundle = step_fn(
            params, state, health, next(it), default_controls())
        b = bundle_dict(bundle)
        first = first if first is not None else b["loss"]
        last = b["loss"]
    assert set(b) == set(BUNDLE_KEYS)
    assert b["applied"] == 1.0 and b["nonfinite"] == 0.0
    assert last < first * 0.5
    assert int(health["count"]) == 25
    assert int(state["step"]) == 25


def test_nonfinite_step_gated_inside_jit():
    """An injected NaN-grad step must leave params, optimizer moments,
    AND the EMA state bit-identical — the gate lives in the jitted
    step, not in host policy."""
    params, state, step_fn = _fresh()
    health = init_health()
    it = iter(_batches())
    for _ in range(3):
        params, state, health, _ = step_fn(params, state, health,
                                           next(it), default_controls())
    # np.array(copy) — np.asarray can alias the donated device buffer
    before = jax.tree.map(lambda x: np.array(x), {"p": params,
                                                  "s": state,
                                                  "h": health})
    ctl = default_controls()
    ctl["inject_nan"] = jnp.float32(1.0)
    params, state, health, bundle = step_fn(params, state, health,
                                            next(it), ctl)
    b = bundle_dict(bundle)
    assert b["nonfinite"] == 1.0 and b["applied"] == 0.0
    assert not np.isfinite(b["grad_norm"])
    after = jax.tree.map(np.asarray, {"p": params, "s": state,
                                      "h": health})
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(x, y)


def test_grad_norm_ceiling_gates_update():
    params, state, step_fn = _fresh()
    health = init_health()
    ctl = default_controls()
    ctl["max_grad_norm"] = jnp.float32(1e-9)    # everything is over
    w_before = np.asarray(params["w"]).copy()   # args are donated
    p2, s2, _, bundle = step_fn(params, state, health,
                                next(iter(_batches())), ctl)
    assert bundle_dict(bundle)["applied"] == 0.0
    np.testing.assert_array_equal(np.asarray(p2["w"]), w_before)
    assert int(s2["step"]) == 0


# ---------------------------------------------------------------------------
# Host classifier + event log
# ---------------------------------------------------------------------------

def _bundle(loss=1.0, gnorm=1.0, spike=0.0, nonfinite=0.0):
    return {"loss": loss, "grad_norm": gnorm, "spike": spike,
            "nonfinite": nonfinite, "applied": 1.0 - nonfinite}


def test_classifier_escalation_ladder():
    mon = HealthMonitor(MonitorConfig(skip_limit=1, max_rollbacks=1,
                                      spike_sigma=4.0, spike_warmup=2))
    assert mon.classify(0, _bundle()) == OK
    assert mon.classify(1, _bundle(nonfinite=1.0)) == SKIP
    # second consecutive bad step exceeds skip_limit=1 -> rollback
    assert mon.classify(2, _bundle(nonfinite=1.0)) == ROLLBACK
    # an ok step resets the skip streak
    assert mon.classify(3, _bundle()) == OK
    assert mon.classify(4, _bundle(nonfinite=1.0)) == SKIP
    # spike after warmup -> rollback; rollback budget (1) exhausted ->
    # escalates to abort
    assert mon.classify(5, _bundle(spike=9.0)) == ABORT
    kinds = [e["verdict"] for e in mon.log.of_kind("verdict")]
    assert kinds == [SKIP, ROLLBACK, SKIP, ABORT]


def test_spike_needs_warmup():
    mon = HealthMonitor(MonitorConfig(spike_sigma=4.0, spike_warmup=3))
    for i in range(3):
        assert mon.classify(i, _bundle(spike=100.0)) == OK
    assert mon.classify(3, _bundle(spike=100.0)) == ROLLBACK


def test_event_log_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("verdict", 3, verdict=SKIP, reason="nonfinite")
    log.emit("checkpoint", 4, dir="x")
    with open(path, encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f]
    assert lines == log.events
    assert lines[0]["kind"] == "verdict" and lines[0]["step"] == 3


# ---------------------------------------------------------------------------
# Checkpoint hardening (satellite: ValueError-based validation)
# ---------------------------------------------------------------------------

def test_load_errors_name_offending_path_and_shape(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, {"w": np.zeros((4, 2), np.float32)}, step=1)
    with pytest.raises(CheckpointError) as e:
        ckpt.load(d, like={"w": jnp.zeros((5, 2), jnp.float32)})
    assert "'w'" in str(e.value) and "(4, 2)" in str(e.value) \
        and "(5, 2)" in str(e.value)
    with pytest.raises(CheckpointError) as e:
        ckpt.load(d, like={"w": jnp.zeros((4, 2)), "b": jnp.zeros(2)})
    assert "'b'" in str(e.value) and "missing" in str(e.value)


def test_manifest_missing_and_truncated_errors(tmp_path):
    with pytest.raises(CheckpointError, match="manifest.msgpack is "
                                              "missing"):
        ckpt.load(str(tmp_path / "nope"))
    d = str(tmp_path / "ck")
    ckpt.save(d, {"w": np.zeros(3, np.float32)}, step=1)
    mpath = os.path.join(d, "manifest.msgpack")
    with open(mpath, "rb") as f:
        blob = f.read()
    with open(mpath, "wb") as f:
        f.write(blob[:len(blob) // 2])          # torn write
    with pytest.raises(CheckpointError, match="corrupt or truncated"):
        ckpt.load(d)


def test_corrupted_shard_detected_by_checksum(tmp_path):
    """Bit rot in a shard must fail the load with the shard named —
    never be silently trained on."""
    d = str(tmp_path / "ck")
    tree = {"w": np.arange(12, dtype=np.float32),
            "b": np.ones(3, np.float32)}
    ckpt.save(d, tree, step=5)
    corrupt_shard(d, 1)                          # 'w' (paths sort b, w)
    with pytest.raises(CheckpointError) as e:
        ckpt.load(d, like=jax.tree.map(jnp.asarray, tree))
    assert "crc32" in str(e.value) and "arr_1.npy" in str(e.value)
    # verify=False is the explicit escape hatch (e.g. forensics)
    restored, step = ckpt.load(d, like=jax.tree.map(jnp.asarray, tree),
                               verify=False)
    assert step == 5


# ---------------------------------------------------------------------------
# CheckpointManager: atomicity, latest(), retention
# ---------------------------------------------------------------------------

def test_manager_latest_retention_and_meta(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    assert mgr.latest() is None
    tree = {"w": jnp.arange(4, dtype=jnp.float32)}
    for s in (2, 4, 6):
        mgr.save(s, tree, meta={"cursor": s * 10})
    assert mgr.steps() == [4, 6]                 # keep=2 retention
    assert mgr.latest().endswith("step_00000006")
    got, step, meta = mgr.restore(tree)
    assert step == 6 and meta["cursor"] == 60
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(tree["w"]))
    # stale/missing LATEST pointer: discovery falls back to a scan
    os.remove(os.path.join(str(tmp_path), "LATEST"))
    assert CheckpointManager(str(tmp_path)).latest() \
        .endswith("step_00000006")


def test_kill_mid_save_leaves_previous_checkpoint_loadable(tmp_path):
    """The crash-safety contract: a save killed mid-shard must leave
    the prior checkpoint fully intact and discoverable, and the torn
    temp dir must be collected on the next manager construction."""
    params, state, step_fn = _fresh()
    tr = ResilientTrainer(
        step_fn, params, state, CursorStream(_batches),
        manager=CheckpointManager(str(tmp_path)),
        injector=FaultInjector(FaultPlan.make(
            [Fault("crash_in_save", 7, arg=2)])),
        ckpt_every=4)
    with pytest.raises(CrashInjected, match="mid-save at step 7"):
        tr.run(20)
    assert any(n.startswith(".tmp-") for n in os.listdir(str(tmp_path)))
    mgr = CheckpointManager(str(tmp_path))       # a fresh process
    assert not any(n.startswith(".tmp-")
                   for n in os.listdir(str(tmp_path)))
    assert mgr.steps() == [4]
    tree, step, meta = mgr.restore(
        {"params": params, "opt": state, "health": init_health()})
    assert step == 4 and meta["cursor"] == 4


def test_fault_plan_json_roundtrip(tmp_path):
    plan = FaultPlan.make([Fault("nan_grads", 3),
                           Fault("crash", 9),
                           Fault("corrupt_shard", 5, arg=2)])
    path = str(tmp_path / "faults.json")
    plan.save(path)
    assert FaultPlan.load(path) == plan
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor", 1)


def test_cursor_stream_seek_replays_exactly():
    s1, s2 = CursorStream(_batches), CursorStream(_batches)
    for _ in range(5):
        b5 = s1.next()
    s2.seek(4)
    np.testing.assert_array_equal(np.asarray(s2.next()["x"]),
                                  np.asarray(b5["x"]))
    assert s1.cursor == s2.cursor == 5


# ---------------------------------------------------------------------------
# The acceptance properties
# ---------------------------------------------------------------------------

def test_resume_equivalence_after_injected_crash(tmp_path):
    """Crash at step 13 (ckpt every 4), resume from latest() — the
    union of pre-crash and post-resume logged losses must equal an
    uninterrupted run's, bit-exactly."""
    ref = _trainer().run(20)["losses"]

    tr = _trainer(tmp_path, faults=[Fault("crash", 13)], ckpt_every=4)
    with pytest.raises(CrashInjected):
        tr.run(20)
    pre = dict(tr.losses)

    tr2 = _trainer(tmp_path, resume=True)
    assert tr2.step == 12                        # latest checkpoint
    post = tr2.run(20)["losses"]

    merged = {**{k: v for k, v in pre.items() if k < tr2.step}, **post}
    assert merged.keys() == ref.keys()
    for k in sorted(ref):
        assert merged[k] == ref[k], (k, merged[k], ref[k])


def test_nan_grad_rollback_and_reconvergence(tmp_path):
    """An injected NaN-grad step is detected, rolled back to the last
    good checkpoint, retried, and the run re-converges."""
    mon = HealthMonitor(MonitorConfig(skip_limit=0))   # bad step ->
    #                                                    rollback now
    tr = _trainer(tmp_path, faults=[Fault("nan_grads", 12)],
                  monitor=mon, ckpt_every=5)
    res = tr.run(30)
    assert res["rollbacks"] == 1
    assert [f["kind"] for f in res["fired_faults"]] == ["nan_grads"]
    restores = mon.log.of_kind("restore")
    assert len(restores) == 1 and restores[0]["step"] == 10
    # every step completed, no NaN ever reached params, loss converged
    assert sorted(res["losses"]) == list(range(30))
    vals = [res["losses"][k] for k in sorted(res["losses"])]
    assert np.isfinite(vals).all()
    assert vals[-1] < vals[0] * 0.1
    # escalating grad clip engaged for the retry
    retries = mon.log.of_kind("retry")
    assert retries and retries[0]["clip_scale"] == 0.5


def test_skip_policy_drops_poisoned_step_and_continues(tmp_path):
    """With skips tolerated, a NaN step is simply dropped: the batch is
    consumed, nothing is applied, and training proceeds without any
    rollback."""
    mon = HealthMonitor(MonitorConfig(skip_limit=3))
    tr = _trainer(tmp_path, faults=[Fault("nan_grads", 6)], monitor=mon)
    res = tr.run(15)
    assert res["rollbacks"] == 0 and res["skipped"] == 1
    assert 6 not in res["losses"]                # dropped, not logged
    assert len(res["losses"]) == 14
    vals = [res["losses"][k] for k in sorted(res["losses"])]
    assert np.isfinite(vals).all() and vals[-1] < vals[0]


def test_abort_after_retry_budget(tmp_path):
    faults = [Fault("nan_grads", s) for s in range(4, 10)]
    mon = HealthMonitor(MonitorConfig(skip_limit=0, max_rollbacks=100))
    tr = _trainer(tmp_path, faults=faults, monitor=mon, ckpt_every=2,
                  policy=RetryPolicy(max_attempts=2))
    with pytest.raises(TrainingAborted, match="retry attempts"):
        tr.run(30)


def test_rollback_without_checkpoint_aborts():
    mon = HealthMonitor(MonitorConfig(skip_limit=0))
    tr = _trainer(None, faults=[Fault("nan_grads", 3)], monitor=mon)
    with pytest.raises(TrainingAborted, match="no checkpoint"):
        tr.run(10)


def test_device_loss_replans_and_resumes(tmp_path):
    """A simulated device loss triggers the replan hook, restores the
    last checkpoint, and the run still completes every step."""
    seen = []
    tr = _trainer(tmp_path, faults=[Fault("device_loss", 9, arg=2)],
                  ckpt_every=4, on_device_loss=seen.append)
    res = tr.run(16)
    assert seen == [2]
    assert res["last_step"] == 16
    assert sorted(res["losses"]) == list(range(16))
    ev = tr.monitor.log
    assert ev.of_kind("device-loss")[0] == {"kind": "device-loss",
                                           "step": 9, "lost": 2}
    assert any(e["why"] == "device-loss" for e in ev.of_kind("restore"))


def test_shrink_plan_degrades_gracefully():
    """The launch driver's device-loss hook: parallelize() re-runs over
    the shrunken ClusterSpec and yields a valid, smaller plan."""
    from repro.launch.train import shrink_plan
    from repro.models.mllm import build_paper_mllm
    from repro.parallel import ClusterSpec, WorkloadShape, parallelize
    mllm = build_paper_mllm("vlm", reduced=True, text_len=32)
    plan = parallelize(mllm, ClusterSpec(num_devices=4),
                       WorkloadShape(text_len=32, num_microbatches=4,
                                     block_size=8))
    args = argparse.Namespace(seq=32, microbatches=4, batch=2)
    # losing more devices than can be spared clamps to the MLLM floor
    # (1 LLM stage + 1 stage per encoder) instead of an infeasible
    # 1-device search
    degraded = shrink_plan(mllm, plan, 2, args)
    assert degraded.pp_devices >= 1 + len(mllm.encoders)
    assert degraded.pp_devices <= plan.pp_devices
    assert degraded.schedule.bubble_fraction >= 0.0
    degraded.apply(mllm, text_len=32)            # still instantiates


# ---------------------------------------------------------------------------
# Driver-level (launch/train): --resume, fault plans, checkpoint fix
# ---------------------------------------------------------------------------

def _lm_argv(tmp, steps, extra=()):
    return ["--arch", "xlstm-125m", "--reduced", "--steps", str(steps),
            "--seq", "16", "--batch", "2", "--vocab", "64",
            "--log-every", "1000", "--ckpt-dir", str(tmp),
            "--ckpt-every", "3", *extra]


def test_driver_resume_equivalence(tmp_path):
    """The --resume acceptance test at the CLI surface: a crash-
    interrupted run resumed with --resume logs the same losses as an
    uninterrupted run."""
    from repro.launch import train
    ref = train.main(_lm_argv(tmp_path / "ref", 8))
    ref_losses = ref["resilience"]["losses"]

    fplan = str(tmp_path / "faults.json")
    FaultPlan.make([Fault("crash", 5)]).save(fplan)
    with pytest.raises(CrashInjected):
        train.main(_lm_argv(tmp_path / "run", 8,
                            ["--fault-plan", fplan]))
    res = train.main(_lm_argv(tmp_path / "run", 8, ["--resume"]))
    post = res["resilience"]["losses"]
    assert post, "resume produced no steps"
    for k, v in post.items():
        assert v == ref_losses[k], (k, v, ref_losses[k])
    # the pre-crash checkpoint at step 3 covered steps the resume
    # didn't re-run; together they span the whole schedule
    assert max(post) == 7


def test_driver_mllm_checkpoint_bundles_everything(tmp_path):
    """Regression for the train_mllm checkpoint bug: the saved
    checkpoint must bundle params + optimizer state + health EMA +
    step/cursor meta (it used to save bare params with frozen_paths
    computed and dropped), and frozen shards must actually be reused
    across checkpoints."""
    from repro.launch import train
    d = tmp_path / "mllm"
    train.main(["--mllm", "vlm", "--reduced", "--steps", "4",
                "--seq", "32", "--batch", "2", "--log-every", "1000",
                "--plan-devices", "2", "--microbatches", "2",
                "--ckpt-dir", str(d), "--ckpt-every", "2"])
    mgr = CheckpointManager(str(d))
    last = mgr.latest()
    assert last.endswith("step_00000004")
    arrays, step = ckpt.load(last)
    assert step == 4
    prefixes = {p.split("/", 1)[0] for p in arrays}
    assert {"params", "opt", "health"} <= prefixes
    meta = ckpt.read_manifest(last)["meta"]
    assert meta["step"] == 4 and meta["cursor"] == 4
    assert "plan" in meta                        # the plan rides along
    # frozen-module shards are hardlinked forward, not rewritten
    man = ckpt.read_manifest(last)
    frozen = [e for e in man["entries"]
              if e["path"].startswith("params/encoders/") or
              e["path"].startswith("params/llm/")]
    assert frozen
    linked = [e for e in frozen if os.stat(
        os.path.join(last, e["file"])).st_nlink > 1]
    assert linked, "no frozen shard was reused across checkpoints"


# ---------------------------------------------------------------------------
# The lookahead: batch n+1 drawn while the device runs step n
# ---------------------------------------------------------------------------

class _Bundle:
    """A health bundle whose host read goes into the log."""

    def __init__(self, log, i, vals):
        self.log, self.i, self.vals = log, i, vals

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.i))
        return np.asarray(self.vals, dtype or np.float32)


class _LoggedStream(CursorStream):
    """Batch k is ``{"i": k}``; every draw the trainer makes is logged
    (a seek's fast-forward is not)."""

    def __init__(self, log, n=None):
        super().__init__(lambda: iter(range(n if n is not None else 10**9)))
        self.log = log

    def next(self):
        k = super().next()
        self.log.append(("draw", k))
        return {"i": np.int32(k)}


def _toy_trainer(log, tmp=None, *, n=None, faults=(), ckpt_every=0,
                 monitor=None, resume=False, on_device_loss=None):
    """A trainer over a host step that logs the batch it saw and adds
    its index to ``w`` (held back under an injected NaN), so the losses
    spell out the order the batches trained in."""
    def step(params, opt_state, health, batch, controls):
        i = int(batch["i"])
        bad = float(controls["inject_nan"]) > 0
        log.append(("step", i))
        w = params["w"]
        new = params if bad else {"w": w + i}
        return new, opt_state, health, _Bundle(
            log, i, [float(w) + i, 1.0, 0.0, float(bad), float(not bad)])

    return ResilientTrainer(
        step, {"w": jnp.float32(0.0)}, {"n": jnp.int32(0)},
        _LoggedStream(log, n), monitor=monitor,
        manager=CheckpointManager(str(tmp)) if tmp is not None else None,
        injector=FaultInjector(FaultPlan.make(list(faults))),
        ckpt_every=ckpt_every, resume=resume,
        on_device_loss=on_device_loss)


def _run(tr, n, calls):
    """``run(n)`` at once, or one step a call as the benchmark does."""
    if calls == "one_call":
        return tr.run(n)
    while tr.step < n:
        res = tr.run(tr.step + 1)
    return res


def _seen(log):
    return [i for kind, i in log if kind == "step"]


@pytest.mark.parametrize("calls", ["one_call", "step_by_step"])
def test_lookahead_draws_the_next_batch_before_the_health_read(calls):
    log = []
    tr = _toy_trainer(log)
    res = _run(tr, 5, calls)
    assert _seen(log) == [0, 1, 2, 3, 4]         # batch k trains step k
    want = [("draw", 0)]
    for k in range(5):
        want += [("step", k), ("draw", k + 1), ("read", k)]
    assert log == want
    assert res["batches_ahead"] == 4 and res["batches_in_series"] == 1
    assert res["losses"] == {k: float(k * (k - 1) // 2 + k)
                             for k in range(5)}
    # batch 5 waits on the trainer, drawn but not consumed
    assert tr.stream.cursor == 6 and tr.cursor == 5


@pytest.mark.parametrize("calls", ["one_call", "step_by_step"])
def test_finite_stream_runs_every_batch_and_ends_as_before(calls):
    log = []
    tr = _toy_trainer(log, n=4)
    res = _run(tr, 4, calls)
    assert _seen(log) == [0, 1, 2, 3] and res["last_step"] == 4
    # the draw past the end found nothing: no batch waits
    assert res["batches_ahead"] == 3 and tr.cursor == tr.stream.cursor == 4
    with pytest.raises(StopIteration):
        tr.run(5)
    assert tr.step == 4


@pytest.mark.parametrize("calls", ["one_call", "step_by_step"])
def test_checkpoint_with_a_batch_ahead_records_consumed_cursor(
        tmp_path, calls):
    ref = _toy_trainer([]).run(8)["losses"]
    tr = _toy_trainer([], tmp_path, faults=[Fault("crash", 5)],
                      ckpt_every=2)
    with pytest.raises(CrashInjected):
        _run(tr, 8, calls)
    # each save came with the next batch drawn ahead, not consumed
    saves = tr.monitor.log.of_kind("checkpoint")
    assert [(e["step"], e["cursor"]) for e in saves] == [(2, 2), (4, 4)]
    assert tr.stream.cursor == 6
    assert CheckpointManager(str(tmp_path)).peek_meta()["cursor"] == 4
    tr2 = _toy_trainer([], tmp_path, resume=True)
    assert tr2.step == tr2.cursor == tr2.stream.cursor == 4
    post = _run(tr2, 8, calls)["losses"]
    merged = {**{k: v for k, v in tr.losses.items() if k < 4}, **post}
    assert merged == ref


def _restore_case(case, log, tmp):
    """(trainer after the event, the batches its steps saw, the batch
    the pass after the event draws again) for each event that moves the
    stream or the placement."""
    if case == "rollback":
        tr = _toy_trainer(log, tmp, faults=[Fault("nan_grads", 3)],
                          ckpt_every=2,
                          monitor=HealthMonitor(MonitorConfig(skip_limit=0)))
        tr.run(6)
        return tr, [0, 1, 2, 3, 2, 3, 4, 5], 2
    if case == "device_loss":
        tr = _toy_trainer(log, tmp, faults=[Fault("device_loss", 3)],
                          ckpt_every=2)
        tr.run(6)
        return tr, [0, 1, 2, 2, 3, 4, 5], 2
    if case == "device_loss_no_checkpoint":
        lost = []
        tr = _toy_trainer(log, faults=[Fault("device_loss", 3)],
                          on_device_loss=lost.append)
        tr.run(6)
        assert lost == [1]
        return tr, [0, 1, 2, 3, 4, 5], 3
    tr = _toy_trainer(log)                       # adopt_state
    tr.run(4)
    tr.adopt_state({"w": jnp.float32(0.0)}, {"n": jnp.int32(0)},
                   step=1, cursor=1)
    assert tr.cursor == tr.stream.cursor == 1
    tr.run(4)
    return tr, [0, 1, 2, 3, 1, 2, 3], 1


@pytest.mark.parametrize("case", ["rollback", "device_loss",
                                  "device_loss_no_checkpoint", "adopt"])
def test_restore_drops_the_batch_ahead(tmp_path, case):
    log = []
    tr, seen, redrawn = _restore_case(case, log, tmp_path)
    assert _seen(log) == seen
    res = tr.summary()
    assert res["batches_in_series"] == 2
    assert res["batches_ahead"] == len(seen) - 2
    # the first pass, and the first after the event, draw their own
    # batch just before they dispatch; the one after the event is the
    # restored cursor's, drawn again though it had been drawn ahead
    serial = [log[j][1] for j in range(1, len(log))
              if log[j - 1] == ("draw", log[j][1]) and log[j][0] == "step"]
    assert serial == [0, redrawn]
    draws = [i for kind, i in log if kind == "draw"]
    assert draws.count(redrawn) == 2
    assert tr.cursor == tr.step == tr.stream.cursor - 1
