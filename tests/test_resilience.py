"""Fault-tolerance runtime (megatron_llm_tpu/resilience.py): fault-injector
spec parsing, spike sentinel + rewind, hang watchdog, samples accounting,
signal-save resume parity, and the end-to-end chaos run (NaN grads +
transient save IOErrors + SIGTERM in one training run)."""

import os
import signal
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu import checkpointing, global_vars
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.dist_signal_handler import DistributedSignalHandler
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.resilience import (
    FaultInjector,
    HangWatchdog,
    ResilienceConfig,
    ResilienceManager,
    recovery_counters,
    set_save_fault_hook,
)
from megatron_llm_tpu.training import pretrain


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    global_vars.reset_counters()
    checkpointing.configure_save(total_limit=0, retries=2,
                                 retry_backoff=0.01)
    yield
    set_save_fault_hook(None)
    global_vars.reset_counters()
    checkpointing.configure_save(total_limit=0, retries=2,
                                 retry_backoff=0.25)


def _setup(utils):
    cfg = llama_config("tiny", seq_length=16, max_position_embeddings=16,
                       padded_vocab_size=64, num_layers=1, hidden_size=32,
                       num_attention_heads=4, ffn_hidden_size=64)
    model = LlamaModel(cfg)
    utils.initialize_model_parallel(tp=1)
    # shard at init (as the CLI drivers do): the train step then compiles
    # exactly once, instead of re-tracing when step-1 outputs come back
    # with mesh shardings the init params lacked
    params = model.init(jax.random.PRNGKey(0))
    params = sh.shard_params(params, model.param_specs(params))

    def it():
        # per-generator RNG: every it() call replays the same stream, so
        # an interrupted run can rebuild its data position exactly
        rng = np.random.RandomState(0)
        while True:
            toks = jnp.asarray(rng.randint(0, 64, size=(1, 8, 16)))
            yield {
                "tokens": toks,
                "labels": jnp.roll(toks, -1, axis=-1),
                "loss_mask": jnp.ones_like(toks, jnp.float32),
            }

    return model, params, it


def _tc(iters):
    return TrainConfig(micro_batch_size=8, global_batch_size=8,
                       train_iters=iters, lr=1e-2, optimizer="adam", seed=3)


def _flat(params):
    return np.concatenate([np.asarray(jnp.asarray(l)).ravel()
                           for l in jax.tree_util.tree_leaves(params)])


def _load_with_opt(load_dir, train_cfg, model):
    """Host-restore params + optimizer state (the CLI resume shape: the
    optimizer exists only after params do, so load goes in two phases),
    re-placed onto the current mesh exactly as finetune.py's resume does."""
    from jax.sharding import NamedSharding, PartitionSpec

    pl, _, meta = checkpointing.load_checkpoint(load_dir)
    assert pl is not None
    pl = sh.shard_params(jax.tree_util.tree_map(jnp.asarray, pl),
                         model.param_specs(pl))
    opt = MegatronOptimizer(train_cfg)
    tmpl = jax.eval_shape(opt.init, pl)
    _, ol, _ = checkpointing.load_checkpoint(
        load_dir, load_params=False, opt_state_template=tmpl)
    mesh = jax.tree_util.tree_leaves(pl)[0].sharding.mesh

    def _replicated(t):
        return jax.device_put(
            t, NamedSharding(mesh, PartitionSpec(*([None] * t.ndim))))

    psh = jax.tree_util.tree_map(lambda p: p.sharding, pl)

    def _like_params(tree):
        if tree is None:
            return None
        return jax.tree_util.tree_map(jax.device_put, tree, psh)

    ol = ol._replace(
        step=_replicated(ol.step),
        master_params=_like_params(ol.master_params),
        exp_avg=_like_params(ol.exp_avg),
        exp_avg_sq=_like_params(ol.exp_avg_sq),
        grad_scaler=jax.tree_util.tree_map(_replicated, ol.grad_scaler),
    )
    return pl, ol, opt, meta


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------

def test_fault_injector_spec_parsing():
    inj = FaultInjector.from_spec("nan@3,save_io*2,hang@5:2.0,sigterm@7")
    assert inj.nan_iters == {3}
    assert inj.save_io_failures == 2
    assert inj.hang_at == 5 and inj.hang_secs == 2.0
    assert inj.sigterm_at == 7
    assert FaultInjector.from_spec("") is None
    assert FaultInjector.from_spec(None) is None
    assert FaultInjector.from_spec("hang@4").hang_secs == 1.0
    with pytest.raises(ValueError):
        FaultInjector.from_spec("explode@9")


def test_fault_injector_poison_once_and_save_io_budget():
    inj = FaultInjector.from_spec("nan@2,save_io*1")
    batch = {"loss_mask": np.ones((2, 2), np.float32)}
    assert inj.poison_batch(1, batch) is batch          # untouched
    poisoned = inj.poison_batch(2, batch)
    assert np.all(np.isnan(poisoned["loss_mask"]))
    assert np.all(batch["loss_mask"] == 1.0)            # original intact
    # one-shot: the replayed iteration 2 after a rewind stays clean
    assert inj.poison_batch(2, batch) is batch
    with pytest.raises(IOError):
        inj.maybe_fail_save()
    inj.maybe_fail_save()                               # budget spent


# ---------------------------------------------------------------------------
# Sentinel / rewind units
# ---------------------------------------------------------------------------

def test_sentinel_flags_nonfinite_and_spike():
    rm = ResilienceManager(ResilienceConfig(spike_factor=3.0, patience=1))
    assert not rm.record_metrics(1, 1.0)
    assert not rm.record_metrics(2, 1.1)                # mild rise: fine
    assert rm.record_metrics(3, float("nan"))
    assert rm.record_metrics(4, 1.0, grad_norm=float("inf"))
    assert rm.record_metrics(5, 50.0)                   # spike vs ~1.0 EMA
    # no snapshot yet -> never rewind, however bad the streak
    assert not rm.should_rewind()


def test_sentinel_patience_and_streak_reset():
    rm = ResilienceManager(ResilienceConfig(spike_factor=0.0, patience=2))
    rm.take_snapshot(0, {"w": jnp.zeros((2,), jnp.float32)}, None)
    rm.record_metrics(1, 1.0)
    assert rm.record_metrics(2, float("nan"))
    assert not rm.should_rewind()        # streak 1 < patience 2
    rm.record_metrics(3, 1.0)            # good step resets the streak
    assert rm.record_metrics(4, float("nan"))
    assert not rm.should_rewind()
    assert rm.record_metrics(5, float("nan"))
    assert rm.should_rewind()            # streak reached patience


def test_snapshot_rejects_nonfinite_params():
    rm = ResilienceManager(ResilienceConfig())
    good = {"w": jnp.ones((2, 2), jnp.float32)}
    bad = {"w": jnp.full((2, 2), jnp.nan, jnp.float32)}
    assert rm.take_snapshot(1, good, None)
    assert rm.snapshot_iteration == 1
    assert not rm.take_snapshot(2, bad, None)
    assert rm.snapshot_iteration == 1    # old known-good snapshot kept


def test_rewind_restores_snapshot_and_scales_lr():
    rm = ResilienceManager(
        ResilienceConfig(patience=1, rewind_lr_factor=0.5, spike_factor=0))
    rm.take_snapshot(3, {"w": jnp.ones((2, 2), jnp.float32)}, None)
    live = {"w": jnp.full((2, 2), 7.0, jnp.float32)}
    rm.record_metrics(4, float("nan"))
    assert rm.should_rewind()
    p, o, it = rm.rewind(live, None)
    assert it == 3 and o is None
    np.testing.assert_array_equal(np.asarray(p["w"]), np.ones((2, 2)))
    assert rm.lr_scale == 0.5
    assert recovery_counters()["rewinds"] == 1


def test_rewind_hard_stops_at_max_rewinds():
    rm = ResilienceManager(
        ResilienceConfig(patience=1, max_rewinds=1, spike_factor=0))
    p0 = {"w": jnp.zeros((2,), jnp.float32)}
    rm.take_snapshot(0, p0, None)
    rm.rewind(p0, None)
    with pytest.raises(RuntimeError, match="max_rewinds"):
        rm.rewind(p0, None)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

def test_watchdog_fires_and_dumps():
    fired = []
    lines = []
    wd = HangWatchdog(timeout_secs=0.15, on_fire=lambda: fired.append(1),
                      hard_exit=False, poll_interval=0.03,
                      printer=lines.append)
    wd.start()
    try:
        deadline = time.monotonic() + 3.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        wd.stop()
    assert wd.fired and fired == [1]
    assert wd.last_dump and "python stacks" in wd.last_dump
    assert any("device memory" in l for l in lines)
    assert recovery_counters()["watchdog_fires"] == 1


def test_watchdog_progress_and_pause_prevent_fire():
    wd = HangWatchdog(timeout_secs=0.25, hard_exit=False,
                      poll_interval=0.03, printer=lambda s: None)
    wd.start()
    try:
        for _ in range(8):
            time.sleep(0.05)
            wd.progress()
        assert not wd.fired
        wd.pause()                       # disarmed: no fire while paused
        time.sleep(0.4)
        assert not wd.fired
    finally:
        wd.stop()


# ---------------------------------------------------------------------------
# Train-loop integration
# ---------------------------------------------------------------------------

def test_pretrain_counts_samples(utils):
    model, params, it = _setup(utils)
    pretrain(model, params, _tc(3), ParallelConfig(), it(), log_interval=0)
    c = global_vars.get_counters()
    assert c["samples"] == 3 * 8          # batch [1 micro, 8 seqs, 16 toks]
    assert c["tokens"] == 3 * 8 * 16


def test_nan_injection_triggers_rewind_and_run_completes(utils):
    model, params, it = _setup(utils)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1, patience=1, spike_factor=0),
        injector=FaultInjector.from_spec("nan@3"))
    try:
        p, o, n = pretrain(model, params, _tc(6), ParallelConfig(), it(),
                           log_interval=1, resilience=rm)
    finally:
        rm.close()
    assert n == 6
    assert recovery_counters()["rewinds"] == 1
    assert np.all(np.isfinite(_flat(p)))


class _StallClock:
    """Stands in for the ``time`` module inside ``resilience``: the
    watchdog's clock moves only when the injected stall advances it, so
    however slowly a loaded box runs the steps before, the watchdog
    cannot fire early; and the stall itself (``FaultInjector``'s
    ``time.sleep``) lasts until ``until()`` holds, so however slowly the
    watchdog thread is scheduled, the step does not resume before it."""

    def __init__(self, until):
        self._now = 0.0
        self._until = until

    def monotonic(self):
        return self._now

    def sleep(self, secs):
        self._now += secs
        deadline = time.monotonic() + 120.0
        while not self._until():
            assert time.monotonic() < deadline, "the watchdog never fired"
            time.sleep(0.01)

    def __getattr__(self, name):
        return getattr(time, name)


def test_watchdog_rescue_save_in_pretrain(utils, tmp_path, monkeypatch):
    """A step stalled past the watchdog budget rescue-saves the latest host
    snapshot (hard_exit off so the test can inspect the aftermath)."""
    from megatron_llm_tpu import resilience

    model, params, it = _setup(utils)
    tracker = checkpointing.get_checkpoint_tracker_filename(str(tmp_path))
    # the stall ends when the rescue checkpoint is whole on disk: the
    # tracker is written last
    monkeypatch.setattr(resilience, "time", _StallClock(
        lambda: os.path.isfile(tracker) and os.path.getsize(tracker) > 0))
    wd = HangWatchdog(timeout_secs=0.5, hard_exit=False,
                      poll_interval=0.05, printer=lambda s: None)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1),
        injector=FaultInjector.from_spec("hang@3:2.0"),
        watchdog=wd)
    try:
        pretrain(model, params, _tc(4), ParallelConfig(), it(),
                 log_interval=1, save_dir=str(tmp_path), resilience=rm)
    finally:
        rm.close()
    assert recovery_counters()["watchdog_fires"] == 1
    # the rescue checkpoint holds the snapshot taken before the stall
    pl, _, meta = checkpointing.load_checkpoint(str(tmp_path))
    assert pl is not None and meta["iteration"] == 2


def test_signal_save_resume_parity(utils, tmp_path):
    """straight N iters == (SIGTERM save-and-exit at k) + (restore + skip
    consumed data + finish), bit-close params.  The save goes through the
    hardened path (tmp dir + atomic rename + manifest) and the resume
    through validation."""
    pc = ParallelConfig()

    model, params, it = _setup(utils)
    p_straight, _, _ = pretrain(model, params, _tc(4), pc, it(),
                                log_interval=0)
    straight = _flat(p_straight)

    # interrupted run: SIGTERM lands before iteration 3 runs; the loop
    # finishes the iteration, saves at 3 at the boundary, and exits
    model_b, params_b, it_b = _setup(utils)
    rm = ResilienceManager(ResilienceConfig(),
                           injector=FaultInjector.from_spec("sigterm@3"),
                           rewind_enabled=False)
    with DistributedSignalHandler() as handler:
        with pytest.raises(SystemExit):
            try:
                pretrain(model_b, params_b, _tc(4), pc, it_b(),
                         log_interval=1, save_dir=str(tmp_path),
                         exit_signal_handler=handler, resilience=rm)
            finally:
                rm.close()
    assert recovery_counters()["signal_saves"] == 1

    pl, ol, opt, meta = _load_with_opt(str(tmp_path), _tc(4), model_b)
    assert meta["iteration"] == 3
    gen = it_b()
    for _ in range(meta["iteration"]):    # data the first run consumed
        next(gen)
    p_resumed, _, _ = pretrain(model_b, pl, _tc(4), pc, gen,
                               log_interval=0, start_iteration=3,
                               opt_state=ol, optimizer=opt)
    np.testing.assert_allclose(_flat(p_resumed), straight, atol=1e-6)


@pytest.mark.parametrize("consensus", [False, True])
def test_signals_received_single_host(consensus):
    with DistributedSignalHandler() as h:
        assert h.signals_received(consensus=consensus) is False
        os.kill(os.getpid(), signal.SIGTERM)
        # single host: the local flag is the answer with or without
        # consensus (the allgather only exists for process_count > 1)
        assert h.signals_received(consensus=consensus) is True


def test_chaos_end_to_end(utils, tmp_path):
    """ISSUE acceptance: one run absorbs a NaN-grad iteration, two
    transient save IOErrors, and a SIGTERM — and still reaches
    train_iters with a loadable final checkpoint, reporting exactly
    1 rewind, 2 save retries, 1 signal save."""
    pc = ParallelConfig()
    model, params, it = _setup(utils)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1, patience=1, spike_factor=0),
        injector=FaultInjector.from_spec("nan@2,save_io*2,sigterm@5"))
    gen = it()
    with DistributedSignalHandler() as handler:
        with pytest.raises(SystemExit):
            try:
                pretrain(model, params, _tc(8), pc, gen,
                         log_interval=1, save_dir=str(tmp_path),
                         exit_signal_handler=handler, resilience=rm)
            finally:
                rm.close()

    # phase-1 verdict: rewound once, the signal save survived 2 IOErrors
    c = recovery_counters()
    assert c["rewinds"] == 1
    assert c["save_retries"] == 2
    assert c["signal_saves"] == 1
    assert not list(tmp_path.glob("*.tmp"))       # atomic publish, no debris

    pl, ol, opt, meta = _load_with_opt(str(tmp_path), _tc(8), model)
    resume_at = meta["iteration"]
    assert 0 < resume_at < 8

    # phase 2: restore and run to completion (same data stream object)
    p_final, o_final, n = pretrain(model, pl, _tc(8), pc, gen,
                                   log_interval=1,
                                   start_iteration=resume_at,
                                   opt_state=ol, optimizer=opt)
    assert n == 8
    assert np.all(np.isfinite(_flat(p_final)))
    checkpointing.save_checkpoint(str(tmp_path), n, p_final, o_final)
    _, _, meta2 = checkpointing.load_checkpoint(str(tmp_path))
    assert meta2["iteration"] == 8
