"""Checkpoint save/load with a Megatron-compatible *logical* layout.

Reference: ``megatron/checkpointing.py`` — directory layout
``<save>/iter_{it:07d}/mp_rank_{tp:02d}[_{pp:03d}]/model_optim_rng.pt`` plus
``latest_checkpointed_iteration.txt`` (:77-140,170-174); saved payload is
{args, checkpoint_version, iteration, model state, optimizer state, rng}
(:243-337); ``--finetune`` resets iteration/optim/rng, ``--use_checkpoint_args``
re-hydrates model hyperparams (:482-567).

TPU design: device state is *logically global* (one pytree) — there is no
per-(tp, pp) shard file because resharding is free: load with any new mesh
and ``jax.device_put`` lays it out.  The on-disk format is therefore a
single Orbax/tensorstore tree per iteration:

    <save>/iter_0000100/model/       (orbax pytree: params)
    <save>/iter_0000100/optim/       (orbax pytree: optimizer state)
    <save>/iter_0000100/meta.json    (iteration, args, scheduler, counters,
                                      checkpoint_version, rng seed state)
    <save>/latest_checkpointed_iteration.txt

which *subsumes* ``tools/checkpoint_util.py``'s offline resharder (a
tp=2,pp=4 -> tp=8,pp=1 reshard is just save+load); an explicit
``tools/checkpoint_util.py`` CLI is still provided for parity, plus
Megatron-layout import/export in ``weights_conversion/``.
Orbax writes are multi-host-aware (each host writes its owned shards) —
replacing the reference's "DP rank 0 writes" convention (:267-269).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import time
from pathlib import Path
from typing import Any, Optional, Tuple

import jax
import numpy as np

from megatron_llm_tpu import tracing
from megatron_llm_tpu.global_vars import get_counters
from megatron_llm_tpu.optimizer.optimizer import map_param_trees
from megatron_llm_tpu.parallel import glu_pairs

CHECKPOINT_VERSION = 4.0  # reference latest is 3.0; 4.0 marks the TPU layout

# Hardened-IO knobs (wired from the CLI via configure_save).  total_limit=0
# keeps every checkpoint; retries>0 re-attempts a failed save with
# exponential backoff (transient storage errors are the norm at pod scale,
# MegaScale §4) — every retry increments counters['save_retries'].
_SAVE_CONFIG = {"total_limit": 0, "retries": 2, "retry_backoff": 0.25}


def configure_save(total_limit: Optional[int] = None,
                   retries: Optional[int] = None,
                   retry_backoff: Optional[float] = None) -> None:
    if total_limit is not None:
        _SAVE_CONFIG["total_limit"] = int(total_limit)
    if retries is not None:
        _SAVE_CONFIG["retries"] = int(retries)
    if retry_backoff is not None:
        _SAVE_CONFIG["retry_backoff"] = float(retry_backoff)


def _fault_hook_check() -> None:
    """Chaos hook: resilience.FaultInjector (when active) raises a
    transient IOError here to exercise the retry path."""
    try:
        from megatron_llm_tpu.resilience import get_save_fault_hook
    except Exception:
        return
    hook = get_save_fault_hook()
    if hook is not None:
        hook()


def get_checkpoint_name(save_dir: str, iteration: int, release: bool = False) -> str:
    # reference: checkpointing.py:77-106
    if release:
        return os.path.join(save_dir, "release")
    return os.path.join(save_dir, f"iter_{iteration:07d}")


def get_checkpoint_tracker_filename(save_dir: str) -> str:
    # reference: checkpointing.py:170-174
    return os.path.join(save_dir, "latest_checkpointed_iteration.txt")


def _orbax():
    import orbax.checkpoint as ocp

    return ocp


def config_to_args(cfg) -> dict:
    """JSON-safe dict of a (dataclass) model config, for meta.json 'args'.
    Enums and other rich values degrade to strings; the consumers
    (megatron_ckpt export, model rebuild on import) read plain fields."""
    import dataclasses

    def safe(v):
        if isinstance(v, (bool, int, float, str)) or v is None:
            return v
        if isinstance(v, (list, tuple)):
            return [safe(x) for x in v]
        name = getattr(v, "name", None)     # Enum -> member name
        return name.lower() if isinstance(name, str) else str(v)

    if dataclasses.is_dataclass(cfg):
        return {k: safe(v) for k, v in dataclasses.asdict(cfg).items()}
    if isinstance(cfg, dict):
        return {k: safe(v) for k, v in cfg.items()}
    return {}


# -- integrity manifest -----------------------------------------------------

def _tree_manifest(tree) -> dict:
    """{leaf path: {shape, dtype}} — cheap (aval metadata only, no device
    transfer), written into meta.json and verified on load so silent
    corruption / truncation of a tensorstore dir is caught before training
    resumes on garbage."""
    if tree is None:
        return {}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in leaves:
        if leaf is None:
            continue
        out[jax.tree_util.keystr(path)] = {
            "shape": list(getattr(leaf, "shape", ()) or ()),
            "dtype": str(getattr(leaf, "dtype", np.dtype(type(leaf)))),
        }
    return out


def _manifest_sha256(manifest: dict) -> str:
    blob = json.dumps(manifest, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def _verify_leaves(tree, manifest_section: dict, label: str) -> None:
    """Per-leaf shape/dtype check of a restored tree against the saved
    manifest; raises on any mismatch (a wrong-shape restore must never
    silently enter the optimizer)."""
    if not manifest_section or tree is None:
        return
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if leaf is None:
            continue
        want = manifest_section.get(jax.tree_util.keystr(path))
        if want is None:
            continue
        got_shape = list(getattr(leaf, "shape", ()) or ())
        got_dtype = str(getattr(leaf, "dtype", np.dtype(type(leaf))))
        if got_shape != want["shape"] or got_dtype != want["dtype"]:
            raise ValueError(
                f"checkpoint leaf {label}{jax.tree_util.keystr(path)} "
                f"mismatches its manifest: restored "
                f"{got_shape}/{got_dtype}, saved "
                f"{want['shape']}/{want['dtype']}")


def validate_checkpoint_dir(ckpt_dir) -> Tuple[bool, str]:
    """Structural validation of one iter_* dir: model payload present,
    meta.json parseable, manifest checksum intact.  (ok, reason)."""
    ckpt_dir = Path(ckpt_dir)
    if not (ckpt_dir / "model").exists():
        return False, "missing model/ payload"
    meta_path = ckpt_dir / "meta.json"
    if not meta_path.exists():
        return False, "missing meta.json"
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return False, f"unreadable meta.json ({e})"
    manifest, want = meta.get("manifest"), meta.get("manifest_sha256")
    if manifest is not None and want is not None:
        if _manifest_sha256(manifest) != want:
            return False, "manifest checksum mismatch"
    return True, "ok"


def _iter_checkpoint_dirs(save_dir: str):
    """(iteration, Path) for every iter_* dir, newest first."""
    out = []
    try:
        names = os.listdir(save_dir)
    except OSError:
        return out
    for name in names:
        m = re.fullmatch(r"iter_(\d+)", name)
        if m:
            out.append((int(m.group(1)), Path(save_dir) / name))
    out.sort(reverse=True)
    return out


def _scan_latest_valid(save_dir: str, exclude=None):
    """Newest iter_* dir that passes validation (fallback when the tracker
    or the tracked dir is corrupt).  (iteration, Path) or None."""
    for it, d in _iter_checkpoint_dirs(save_dir):
        if exclude is not None and d == Path(exclude):
            continue
        ok, reason = validate_checkpoint_dir(d)
        if ok:
            return it, d
        print(f" [checkpoint] skipping {d.name}: {reason}", flush=True)
    return None


def _gc_old_checkpoints(save_dir: str) -> None:
    """Keep-last-N: with --save_total_limit set, delete the oldest iter_*
    dirs past the limit (never 'release').  Process 0 only."""
    limit = _SAVE_CONFIG["total_limit"]
    if not limit or limit <= 0 or jax.process_index() != 0:
        return
    dirs = _iter_checkpoint_dirs(save_dir)      # newest first
    for it, d in dirs[limit:]:
        print(f" [checkpoint] save_total_limit={limit}: removing "
              f"{d.name}", flush=True)
        shutil.rmtree(d, ignore_errors=True)


def _commit_checkpoint(save_dir: str, iteration: int, release: bool,
                       tmp_dir, final_dir) -> None:
    """Atomic publish: tmp dir -> final name (os.replace), then tracker,
    then GC.  A crash before the rename leaves only a *.tmp dir the
    loader never considers; a crash after it leaves a fully-valid
    checkpoint the tracker may or may not point at — the fallback scan
    finds it either way."""
    if jax.process_index() != 0:
        return
    final_dir = Path(final_dir)
    if final_dir.exists():
        shutil.rmtree(final_dir)
    os.replace(tmp_dir, final_dir)
    with open(get_checkpoint_tracker_filename(save_dir), "w") as f:
        f.write("release" if release else str(iteration))
    _gc_old_checkpoints(save_dir)


# Async-save state: two AsyncCheckpointers (model + optim proceed
# concurrently), one at-most-one pending tracker slot, and an inflight
# flag so finalize waits for the checkpointers even if a dispatch died
# before the slot was recorded.
_ASYNC = {"model": None, "optim": None, "slot": None, "inflight": False}


def _async_checkpointers():
    ocp = _orbax()
    if _ASYNC["model"] is None:
        _ASYNC["model"] = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
        _ASYNC["optim"] = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
    return _ASYNC["model"], _ASYNC["optim"]


def finalize_async_saves() -> None:
    """Block until the in-flight async save is durable, THEN write its
    tracker file — a crash mid-async-save must never leave the tracker
    pointing at an incomplete checkpoint.  No-op when nothing is
    pending; the train loop calls this in a finally block so every exit
    path (incl. exceptions) flushes."""
    if not (_ASYNC["inflight"] or _ASYNC["slot"]):
        return
    with tracing.span("checkpoint_finalize", "checkpoint"):
        for key in ("model", "optim"):
            if _ASYNC[key] is not None:
                _ASYNC[key].wait_until_finished()
        _ASYNC["inflight"] = False
        if _ASYNC["slot"] is not None:
            save_dir, iteration, release, tmp_dir, final_dir = _ASYNC["slot"]
            _ASYNC["slot"] = None
            _commit_checkpoint(save_dir, iteration, release, tmp_dir,
                               final_dir)


def save_checkpoint(
    save_dir: str,
    iteration: int,
    params,
    opt_state=None,
    scheduler=None,
    *,
    args: Optional[dict] = None,
    consumed_samples: int = 0,
    release: bool = False,
    async_save: bool = False,
) -> str:
    """Reference: save_checkpoint (checkpointing.py:243-337).

    ``async_save`` (beyond-reference): the tensorstore writes proceed in
    the background while training continues; the rename + tracker happen
    only at ``finalize_async_saves()`` (called automatically before the
    next async save, and by the train loop on every exit path).  jax
    arrays are snapshot at call time, so the training step may donate/
    overwrite the live buffers immediately.

    Hardened IO: everything is written into ``iter_NNN.tmp`` and atomically
    renamed into place only once complete, so readers never observe a
    half-written checkpoint; transient IO errors are retried with
    exponential backoff (``configure_save``), counted in
    ``counters['save_retries']``."""
    ocp = _orbax()
    final_dir = Path(get_checkpoint_name(save_dir, iteration, release)).absolute()
    tmp_dir = final_dir.with_name(final_dir.name + ".tmp")
    final_dir.parent.mkdir(parents=True, exist_ok=True)

    # a checkpoint holds the public form of the tree whatever the trainer
    # held (parallel/glu_pairs.py): it loads at any tp, in the serving
    # engine and in weights_conversion/*
    params = glu_pairs.flat(params)
    opt_state = map_param_trees(glu_pairs.flat, opt_state)
    opt_tree = _opt_state_to_tree(opt_state) if opt_state is not None else None
    manifest = {"model": _tree_manifest(params),
                "optim": _tree_manifest(opt_tree)}
    meta = {
        "checkpoint_version": CHECKPOINT_VERSION,
        "iteration": iteration,
        "consumed_samples": int(consumed_samples),
        "args": args or {},
        "opt_param_scheduler": scheduler.state_dict() if scheduler else None,
        "manifest": manifest,
        "manifest_sha256": _manifest_sha256(manifest),
    }

    retries = max(0, _SAVE_CONFIG["retries"])
    for attempt in range(retries + 1):
        try:
            _fault_hook_check()
            if jax.process_count() > 1:
                # multi-process (fleet rescue) saves: only process 0 preps
                # the tmp dir, and a barrier keeps the other hosts from
                # writing into it while the cleanup runs
                if jax.process_index() == 0:
                    if tmp_dir.exists():
                        shutil.rmtree(tmp_dir)
                    tmp_dir.mkdir(parents=True)
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices(
                    f"ckpt_tmp_{iteration}_{attempt}")
            else:
                if tmp_dir.exists():
                    shutil.rmtree(tmp_dir)
                tmp_dir.mkdir(parents=True)
            if async_save:
                # at most one outstanding save: the previous one becomes
                # durable (rename + tracker) before this one starts;
                # inflight is set BEFORE dispatch so a failure below still
                # makes finalize wait
                finalize_async_saves()
                m_ckptr, o_ckptr = _async_checkpointers()
                _ASYNC["inflight"] = True
            else:
                m_ckptr = o_ckptr = ocp.PyTreeCheckpointer()
            with tracing.span("checkpoint_write", "checkpoint",
                              iteration=int(iteration), attempt=attempt,
                              async_save=async_save):
                m_ckptr.save(tmp_dir / "model", params, force=True)
                if opt_tree is not None:
                    # drop None subtrees (sgd has no exp_avg_sq etc.)
                    o_ckptr.save(tmp_dir / "optim", opt_tree, force=True)
                if jax.process_index() == 0:
                    with open(tmp_dir / "meta.json", "w") as f:
                        json.dump(meta, f, indent=1)
            break
        except (IOError, OSError) as e:
            if async_save:
                # drain whatever the dispatch started before reusing tmp
                for key in ("model", "optim"):
                    if _ASYNC[key] is not None:
                        try:
                            _ASYNC[key].wait_until_finished()
                        except Exception:
                            pass
                _ASYNC["inflight"] = False
            if attempt >= retries:
                raise
            get_counters()["save_retries"] += 1
            delay = _SAVE_CONFIG["retry_backoff"] * (2 ** attempt)
            print(f" [checkpoint] save attempt {attempt + 1}/{retries + 1} "
                  f"failed ({e}); retrying in {delay:.2f}s", flush=True)
            time.sleep(delay)

    if async_save:
        _ASYNC["slot"] = (save_dir, iteration, release,
                          str(tmp_dir), str(final_dir))
    else:
        _commit_checkpoint(save_dir, iteration, release, tmp_dir, final_dir)

    # elastic resume: record the fleet shape that produced this checkpoint
    # (run_shape.json at the save-dir root; best effort, process 0 only)
    # so the next run can detect + log a dp x slice change on load
    try:
        from megatron_llm_tpu import multislice
        shape = multislice.run_shape_from_mesh()
        if shape:
            multislice.write_run_shape(save_dir, shape)
    except Exception:
        pass
    return str(final_dir)


def load_checkpoint_args(load_dir: str,
                         iteration: Optional[int] = None) -> dict:
    """The 'args' dict recorded in a checkpoint's meta.json, without
    loading any tensors (reference --use_checkpoint_args,
    checkpointing.py:520-560 reads args from the state dict)."""
    release = False
    if iteration is None:
        iteration, release = read_tracker(load_dir)
        if iteration is None and not release:
            return {}
    ckpt_dir = Path(get_checkpoint_name(load_dir, iteration or 0, release))
    meta_path = ckpt_dir / "meta.json"
    if not meta_path.exists():
        return {}
    with open(meta_path) as f:
        return json.load(f).get("args") or {}


def read_tracker(load_dir: str) -> Tuple[Optional[int], bool]:
    # reference: checkpointing.py:570-607
    tracker = get_checkpoint_tracker_filename(load_dir)
    if not os.path.isfile(tracker):
        return None, False
    try:
        with open(tracker) as f:
            s = f.read().strip()
    except OSError as e:
        print(f" [checkpoint] WARNING: unreadable tracker {tracker} ({e}); "
              f"treating as absent", flush=True)
        return None, False
    if s == "release":
        return None, True
    try:
        return int(s), False
    except ValueError:
        # empty/corrupt tracker (killed mid-write, bad copy): not fatal —
        # the loader falls back to scanning iter_* dirs
        print(f" [checkpoint] WARNING: corrupt tracker {tracker} "
              f"(contents {s!r}); treating as absent", flush=True)
        return None, False


def load_checkpoint(
    load_dir: str,
    *,
    iteration: Optional[int] = None,
    release: bool = False,
    params_template=None,
    opt_state_template=None,
    scheduler=None,
    finetune: bool = False,
    load_params: bool = True,
):
    """Load the latest (or given) checkpoint.

    Returns (params, opt_state, meta).  ``finetune=True`` skips optimizer /
    scheduler / iteration state (reference: --finetune, checkpointing.py:621+).
    Templates (abstract pytrees with shardings) make orbax restore
    direct-to-device with the current mesh layout — resharding on load.

    Resilient load: when no explicit iteration is requested and the tracker
    is missing/corrupt or points at a checkpoint that fails validation
    (missing payload, unreadable meta.json, manifest checksum mismatch),
    the newest iter_* dir that *does* validate is used instead.  An
    explicitly requested iteration is never silently substituted.
    """
    ocp = _orbax()
    explicit = iteration is not None or release
    if not explicit:
        iteration, release = read_tracker(load_dir)
        ckpt_dir = None
        if iteration is not None or release:
            cand = Path(get_checkpoint_name(
                load_dir, iteration or 0, release)).absolute()
            ok, reason = validate_checkpoint_dir(cand)
            if ok:
                ckpt_dir = cand
            else:
                print(f" [checkpoint] WARNING: tracked checkpoint "
                      f"{cand.name} invalid ({reason}); scanning for the "
                      f"newest valid one", flush=True)
        if ckpt_dir is None:
            # the invalid tracked dir fails validation again in the scan,
            # so it is skipped naturally — no exclusion needed
            found = _scan_latest_valid(load_dir)
            if found is None:
                return None, None, None
            iteration, ckpt_dir = found
            release = False
            print(f" [checkpoint] falling back to {ckpt_dir.name}",
                  flush=True)
    else:
        ckpt_dir = Path(get_checkpoint_name(
            load_dir, iteration or 0, release)).absolute()

    with open(ckpt_dir / "meta.json") as f:
        meta = json.load(f)
    manifest = meta.get("manifest") or {}

    ckptr = ocp.PyTreeCheckpointer()

    def _restore_args_for(template):
        """Orbax RestoreArgs from a template pytree (concrete arrays or
        ShapeDtypeStructs carrying .sharding): restore goes straight to
        device buffers laid out for the *current* mesh — load-time
        resharding, no host round trip, and no orbax 'unsafe when
        restoring on a different topology' warning."""
        import jax

        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=getattr(x, "sharding", None)),
            template,
        )
        return ocp.checkpoint_utils.construct_restore_args(abstract)

    def _host_restore_args(path):
        """No-template restore (conversion/resharding tools, tests): pull
        every leaf to host numpy.  Explicit restore_type keeps orbax off
        its sharding-file path — on a host-side tool there is no device
        topology to mismatch, and no 'unsafe when restoring on a different
        topology' warning to emit."""
        import numpy as np

        try:
            meta_obj = ckptr.metadata(path)
        except Exception:
            meta_obj = None
        # orbax API drift: newer versions wrap the tree in an object with
        # .item_metadata/.tree, older PyTreeCheckpointer.metadata() returns
        # the metadata pytree (a dict) directly
        tree = getattr(meta_obj, "item_metadata", meta_obj)
        tree = getattr(tree, "tree", tree)
        if not isinstance(tree, dict) or not tree:
            # metadata file missing/unreadable (older writer, partial
            # copy): let orbax derive structure itself; the topology
            # warning may fire but the restore still works
            return None
        import jax

        return jax.tree_util.tree_map(
            lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree)

    with tracing.span("checkpoint_load", "checkpoint",
                      iteration=int(iteration or 0)):
        if not load_params:
            # optimizer/scheduler-only restore (second phase of a CLI
            # resume, once the optimizer exists to provide a template)
            params = None
        elif params_template is not None:
            params = ckptr.restore(
                ckpt_dir / "model",
                restore_args=_restore_args_for(params_template))
        else:
            params = ckptr.restore(
                ckpt_dir / "model",
                restore_args=_host_restore_args(ckpt_dir / "model"))
        if params is not None:
            _verify_leaves(params, manifest.get("model"), "model")

        opt_state = None
        if not finetune and (ckpt_dir / "optim").exists() \
                and opt_state_template is not None:
            tmpl_tree = _opt_state_to_tree(opt_state_template)
            tree = ckptr.restore(ckpt_dir / "optim",
                                 restore_args=_restore_args_for(tmpl_tree))
            _verify_leaves(tree, manifest.get("optim"), "optim")
            opt_state = _tree_to_opt_state(tree, opt_state_template)

    if finetune:
        meta["iteration"] = 0
        meta["consumed_samples"] = 0
    elif scheduler is not None and meta.get("opt_param_scheduler"):
        scheduler.load_state_dict(meta["opt_param_scheduler"])
    return params, opt_state, meta


# -- opt-state <-> plain tree (orbax wants no custom NamedTuples) -----------

def _opt_state_to_tree(opt_state) -> dict:
    from megatron_llm_tpu.optimizer.optimizer import OptimizerState

    assert isinstance(opt_state, OptimizerState)
    out = {"step": opt_state.step}
    for name in ("master_params", "exp_avg", "exp_avg_sq"):
        v = getattr(opt_state, name)
        if v is not None:
            out[name] = v
    gs = opt_state.grad_scaler
    out["grad_scaler"] = {
        "scale": gs.scale,
        "growth_tracker": gs.growth_tracker,
        "hysteresis_tracker": gs.hysteresis_tracker,
    }
    return out


def _tree_to_opt_state(tree: dict, template):
    from megatron_llm_tpu.optimizer.grad_scaler import GradScalerState
    from megatron_llm_tpu.optimizer.optimizer import OptimizerState

    gs = tree.get("grad_scaler", {})
    return OptimizerState(
        step=tree["step"],
        master_params=tree.get("master_params"),
        exp_avg=tree.get("exp_avg"),
        exp_avg_sq=tree.get("exp_avg_sq"),
        grad_scaler=GradScalerState(
            scale=gs.get("scale"),
            growth_tracker=gs.get("growth_tracker"),
            hysteresis_tracker=gs.get("hysteresis_tracker"),
        ),
    )
