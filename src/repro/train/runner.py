"""The scan-chunked training runtime.

The legacy driver (``launch/train.py`` before this module existed) ran a
per-step Python loop: every step re-dispatched a jitted function from the
host, double-evaluated at report steps (once for the report, once for the
target-accuracy check), and could only save bare params at the very end —
``checkpoint.load_checkpoint`` was never called on the train path, so no
run could resume.

``Trainer`` replaces that loop:

* **scan-chunked epochs** — ``chunk_size`` optimizer steps run inside ONE
  ``lax.scan`` per host dispatch, so per-step Python/dispatch overhead is
  paid once per chunk (measured by the fig6 scan-chunk ablation). The
  §V-A prefetch carry (the next step's ``Minibatch``) is part of the scan
  state, so sampling overlap needs no per-step Python either.
* **multi-epoch schedules** — ``TrainLoopConfig.epochs`` runs whole
  epochs of ``plan.scfg.steps_per_epoch`` steps; the ``TrainState`` epoch
  counter advances *inside* the scan body, so under the
  without-replacement sampling schedule (``TrainOptions.sample_mode =
  "epoch"``) the §V-A prefetch carry crosses epoch boundaries inside one
  scan dispatch — the paper's carry-across-epochs behavior, with the
  sample still a pure function of ``(seed, epoch, step, dp_index)``.
* **buffer donation** — the ``TrainState`` argument is donated to the
  chunk, so params/optimizer/minibatch buffers are updated in place
  instead of doubling peak memory.
* **eval at chunk boundaries** — one eval per report boundary, used for
  BOTH the report and the target-accuracy stop (the legacy loop's
  double-eval bug is structurally gone).
* **full-state checkpoint/resume** — ``save()`` writes the whole
  ``TrainState`` (params, opt state, step, epoch, prefetch carry) through
  the existing ``checkpoint/ckpt.py`` API; ``restore()`` + ``run()``
  continue bit-identically, because sampling and dropout keys are pure
  functions of ``(seed, epoch, step)`` and both counters travel in the
  state. ``run()`` always persists the final state when a checkpoint
  directory is configured (target-accuracy stops and off-boundary step
  counts included — callers no longer re-derive boundary arithmetic).
* **async checkpointing** — mid-run saves are double-buffered: the driver
  thread snapshots the state into fresh device buffers (an async-dispatched
  on-device copy, so the next chunk's donation cannot invalidate it) and a
  worker thread performs the blocking ``device_get`` + ``.npz`` write,
  overlapping with the next scan chunk. At most one save is in flight —
  the next save (or ``run()``'s exit) joins the previous one first. The
  on-disk file is byte-identical to a synchronous ``save()``.

The loss math is the unchanged 4D path: the non-prefetch body consumes
``fourd.make_loss_fn`` (sampling inside the step), the prefetch body the
``pipeline.make_pipeline_fns`` pair — both through the ONE
``core/forward.py`` engine.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (checkpoint_keys, checkpoint_path, latest_step,
                              load_checkpoint, save_checkpoint)
from repro.core import fourd
from repro.core import pipeline as PL
from repro.obs.tracer import Tracer, phase
from repro.train.state import TrainState, init_train_state

CKPT_NAME = "state"          # full-TrainState checkpoints (vs bare "ckpt")

# indirection for tests: assert the driver thread never blocks on a host
# transfer between chunks (the async-checkpoint acceptance criterion)
_device_get = jax.device_get


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    """Host-side knobs of the runtime (all static). Give the run length as
    ``total_steps`` OR as whole ``epochs`` (of ``plan.scfg.steps_per_epoch``
    optimizer steps each) — exactly one of the two."""

    total_steps: Optional[int] = None
    chunk_size: int = 8        # optimizer steps per lax.scan dispatch
    prefetch: bool = False     # §V-A: fold the sampling carry into the scan
    eval_every: Optional[int] = 0   # steps between evals (0/None = never),
                               # rounded up to the enclosing chunk boundary
    target_acc: Optional[float] = None   # stop once an eval reaches this
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0        # steps between full-state saves (0 = only
                               # the final state), rounded up to the
                               # enclosing chunk boundary
    epochs: Optional[int] = None         # alternative to total_steps
    async_ckpt: bool = True    # overlap mid-run saves with the next chunk
    # epoch-parameterized eval cadence: evaluate every N epochs. The
    # resolved cadence is N * plan.scfg.steps_per_epoch — BIT-IDENTICAL to
    # passing that product as eval_every (mirrors optim/schedules.py's
    # epoch forms). Mutually exclusive with a nonzero eval_every.
    eval_every_epochs: Optional[int] = None

    def __post_init__(self):
        assert (self.total_steps is None) != (self.epochs is None), (
            "give exactly one of total_steps / epochs")
        if self.total_steps is not None:
            assert self.total_steps >= 0
        else:
            assert self.epochs >= 0
        assert self.chunk_size > 0
        if self.eval_every_epochs is not None:
            assert self.eval_every_epochs > 0, (
                "eval_every_epochs must be a positive epoch count")
            assert not self.eval_every, (
                "give the eval cadence as eval_every (steps) OR "
                "eval_every_epochs, not both")
        assert self.target_acc is None or self.eval_every \
            or self.eval_every_epochs, (
                "target_acc is only checked at eval boundaries; set "
                "eval_every or eval_every_epochs")


@dataclasses.dataclass
class RunLog:
    """What ``Trainer.run`` observed: the per-step loss sequence (in step
    order, one entry per optimizer step run), the (step, accuracy) evals,
    whether the target accuracy stopped the run early, and the final-state
    checkpoint path (None when no ckpt_dir is configured)."""

    losses: List[float] = dataclasses.field(default_factory=list)
    evals: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    hit_target: bool = False
    final_ckpt: Optional[str] = None
    # -- tracer-derived timing (per ``run()`` call) --------------------------
    ms_per_step: float = 0.0     # train wall / steps, eval, blocking-ckpt
                                 # and compile time excluded
    eval_s: float = 0.0          # total seconds spent in eval_fn
    ckpt_overlap_s: float = 0.0  # async-ckpt worker seconds HIDDEN behind
                                 # training (io time minus the join waits)
    compile_s: float = 0.0       # seconds the driver thread spent compiling
                                 # (or reading the persistent cache)
    programs: int = 0            # programs built on the driver thread


class Trainer:
    """The runtime over a ``FourDPlan``: build once, then
    ``init_state`` / ``restore`` -> ``run`` -> ``save``.

    ``eval_fn`` defaults to the plan's full-graph eval step
    (``fourd.make_eval_step``); tests inject a counting wrapper.
    """

    def __init__(self, plan: fourd.FourDPlan, optimizer,
                 loop: TrainLoopConfig, *,
                 eval_fn: Optional[Callable] = None,
                 tracer: Optional[Tracer] = None):
        self.plan = plan
        self.optimizer = optimizer
        self.loop = loop
        # host spans at the runtime's boundaries (see obs.tracer), each
        # also a profiler annotation. Per-chunk overhead is one
        # perf_counter pair and one annotation — enabled by default; pass
        # Tracer(enabled=False) to opt out entirely.
        self.tracer = tracer if tracer is not None else Tracer(enabled=True)
        self.steps_per_epoch = plan.scfg.steps_per_epoch
        self.total_steps = (loop.total_steps if loop.total_steps is not None
                            else loop.epochs * self.steps_per_epoch)
        # the ONE resolved eval cadence in steps (0 = never): the epoch
        # form is exactly its step equivalent
        self.eval_every = (loop.eval_every_epochs * self.steps_per_epoch
                           if loop.eval_every_epochs is not None
                           else (loop.eval_every or 0))
        if loop.prefetch:
            self._sample_fn, self._mb_loss_fn = PL.make_pipeline_fns(plan)
        else:
            self._loss_fn = fourd.make_loss_fn(plan, train=True)
        # compressed collectives (TrainOptions.compress int8/int4) carry
        # per-site error-feedback accumulators in the scan state
        self._uses_ef = plan.engine().quantized
        self.eval_fn = eval_fn if eval_fn is not None \
            else fourd.make_eval_step(plan)
        self._chunks = {}          # scan length -> jitted chunk fn
        # double-buffered async save: fresh device buffers per snapshot, so
        # the next chunk's donation cannot invalidate an in-flight fetch
        self._snapshot = jax.jit(
            lambda s: jax.tree.map(lambda x: x.copy(), s))
        self._save_thread: Optional[threading.Thread] = None
        self._save_exc: Optional[BaseException] = None

    # -- state construction --------------------------------------------------

    def init_state(self, params, graph) -> TrainState:
        """Fresh state at step 0 (with the warm-up batch when prefetching,
        zero EF accumulators when collectives are compressed)."""
        mb = (self._sample_fn(graph, jnp.zeros((), jnp.int32))
              if self.loop.prefetch else None)
        ef = fourd.make_ef(self.plan) if self._uses_ef else None
        return init_train_state(params, self.optimizer.init(params), mb, ef)

    def save(self, state: TrainState, directory: Optional[str] = None,
             *, sync: bool = True,
             step: Optional[int] = None) -> Optional[str]:
        """Write the FULL state (params, opt state, step, epoch, prefetch
        carry) atomically; the filename carries the step.

        ``sync=True`` (the public default) blocks until the file is on
        disk and returns its path. ``sync=False`` is the double-buffered
        path ``run()`` uses between chunks: the state is snapshotted into
        fresh device buffers (async dispatch — no host transfer on the
        calling thread) and a worker thread performs the ``device_get`` +
        write, overlapping with the next scan chunk; returns None. Either
        way the previous in-flight save is joined first, so at most one is
        outstanding and files land in step order, byte-identical to the
        synchronous path."""
        directory = directory or self.loop.ckpt_dir
        assert directory, "no checkpoint directory configured"
        self.join_saves()
        # run() passes the host-side step counter so the async path never
        # waits on the device between chunks, not even for a scalar
        step = int(state.step) if step is None else step
        if sync:
            with self.tracer.span("ckpt"):     # blocks the driver thread
                return save_checkpoint(directory, step, _device_get(state),
                                       name=CKPT_NAME)
        snap = self._snapshot(state)

        def work():
            t0 = time.perf_counter()
            try:
                save_checkpoint(directory, step, _device_get(snap),
                                name=CKPT_NAME)
            except BaseException as exc:       # surfaced at the next join
                self._save_exc = exc
            finally:
                # worker io time; the part not later spent in "ckpt_wait"
                # joins was hidden behind training (RunLog.ckpt_overlap_s)
                self.tracer.record("ckpt_io", time.perf_counter() - t0)

        self._save_thread = threading.Thread(
            target=work, name="trainer-async-ckpt", daemon=True)
        self._save_thread.start()
        return None

    def join_saves(self) -> None:
        """Wait for the in-flight async save (if any); re-raise its error."""
        if self._save_thread is not None:
            with self.tracer.span("ckpt_wait"):
                self._save_thread.join()
            self._save_thread = None
        if self._save_exc is not None:
            exc, self._save_exc = self._save_exc, None
            raise exc

    def restore(self, example_state: TrainState,
                directory: Optional[str] = None,
                step: Optional[int] = None, *,
                graph=None) -> Optional[TrainState]:
        """Latest (or given-step) full-state checkpoint, restored into the
        structure/shapes of ``example_state``; None when there is none.

        Prefetch-flag mismatches are handled explicitly instead of leaking
        a raw ``KeyError`` from the npz path lookup:

        * resuming WITH prefetch from a checkpoint written WITHOUT it —
          the saved state has no carry; when ``graph`` is given the warm-up
          batch is rebuilt from the restored (step, epoch) (bit-identical,
          since the carry is a pure function of them), otherwise this
          raises with instructions.
        * resuming WITHOUT prefetch from a checkpoint written WITH it —
          the saved carry is redundant (same pure-function argument) and is
          dropped deliberately.
        """
        directory = directory or self.loop.ckpt_dir
        assert directory, "no checkpoint directory configured"
        if step is None:
            step = latest_step(directory, name=CKPT_NAME)
            if step is None:
                return None
        ckpt_keys = checkpoint_keys(directory, step, name=CKPT_NAME)
        # dataclass fields flatten as GetAttrKey -> a ".minibatch" prefix
        ckpt_has_carry = any(k.split("::")[0].lstrip(".") == "minibatch"
                             for k in ckpt_keys)
        # pre-epoch-counter checkpoints (PR-4 layout) lack the ".epoch"
        # leaf; it is derivable from the step (boundaries sit at fixed
        # multiples of steps_per_epoch), so backfill instead of failing
        backfill_epoch = ".epoch" not in ckpt_keys
        example = example_state
        if backfill_epoch:
            example = dataclasses.replace(example, epoch=None)
        rebuild_carry = False
        if self.loop.prefetch and not ckpt_has_carry:
            if graph is None:
                raise ValueError(
                    f"checkpoint step {step} in {directory} was written "
                    "without the §V-A prefetch carry but this Trainer has "
                    "prefetch=True. Pass graph=... to restore() so the "
                    "warm-up batch can be rebuilt (bit-identical — the "
                    "carry is a pure function of (seed, epoch, step)), or "
                    "resume with prefetch off.")
            example = dataclasses.replace(example, minibatch=None)
            rebuild_carry = True
        # pre-compression checkpoints lack the ".comm_ef" leaves; the EF
        # residuals only shift WHEN quantization error is corrected, so a
        # zero-EF restart is sound — backfill fresh accumulators instead of
        # failing. (A checkpoint WITH EF restored into an uncompressed run
        # drops the extra leaves automatically: example has comm_ef=None.)
        ckpt_has_ef = any(k.split("::")[0].lstrip(".") == "comm_ef"
                          for k in ckpt_keys)
        backfill_ef = self._uses_ef and not ckpt_has_ef
        if backfill_ef:
            example = dataclasses.replace(example, comm_ef=None)
        state, _ = load_checkpoint(directory, step, example,
                                   name=CKPT_NAME)
        if backfill_ef:
            state = dataclasses.replace(state,
                                        comm_ef=fourd.make_ef(self.plan))
        if backfill_epoch:
            state = dataclasses.replace(
                state, epoch=jnp.asarray(state.step, jnp.int32)
                // self.steps_per_epoch)
        if rebuild_carry:
            mb = self._sample_fn(graph, state.step, state.epoch)
            state = dataclasses.replace(state, minibatch=mb)
        return state

    # -- the scan-chunked step -----------------------------------------------

    def compiled_chunk(self, length: int):
        """The jitted ``(state, graph) -> (state', (length,) losses)`` chunk:
        ``length`` optimizer steps in one ``lax.scan``, state donated. At
        most two lengths ever compile per run (the chunk and the final
        remainder)."""
        if length not in self._chunks:
            self._chunks[length] = self._build_chunk(length)
        return self._chunks[length]

    def _build_chunk(self, length: int):
        opt = self.optimizer
        prefetch = self.loop.prefetch
        uses_ef = self._uses_ef
        spe = self.steps_per_epoch

        def chunk(state: TrainState, graph):
            def body(st: TrainState, _):
                if prefetch:
                    if uses_ef:
                        def mean_loss(p):
                            losses, new_ef = self._mb_loss_fn(
                                p, st.minibatch, st.step, st.comm_ef)
                            return losses.mean(), new_ef
                        (loss, new_ef), grads = jax.value_and_grad(
                            mean_loss, has_aux=True)(st.params)
                    else:
                        def mean_loss(p):
                            return self._mb_loss_fn(p, st.minibatch,
                                                    st.step).mean()
                        loss, grads = jax.value_and_grad(mean_loss)(
                            st.params)
                        new_ef = st.comm_ef         # None subtree
                    # prefetch batch t+1: data-independent of the grads
                    # above, so XLA may overlap it with the backward pass.
                    # The epoch of step t+1 is derived here, INSIDE the
                    # scan, so the carry crosses epoch boundaries without
                    # leaving the chunk (paper §V-A).
                    next_mb = self._sample_fn(graph, st.step + 1,
                                              (st.step + 1) // spe)
                else:
                    if uses_ef:
                        def mean_loss(p):
                            losses, new_ef = self._loss_fn(
                                p, graph, st.step, st.epoch, st.comm_ef)
                            return losses.mean(), new_ef
                        (loss, new_ef), grads = jax.value_and_grad(
                            mean_loss, has_aux=True)(st.params)
                    else:
                        def mean_loss(p):
                            return self._loss_fn(p, graph, st.step,
                                                 st.epoch).mean()
                        loss, grads = jax.value_and_grad(mean_loss)(
                            st.params)
                        new_ef = st.comm_ef         # None subtree
                    next_mb = st.minibatch          # None subtree
                with phase("optim"):
                    params, opt_state = opt.update(st.params, grads,
                                                   st.opt_state)
                return TrainState(params, opt_state, st.step + 1,
                                  next_mb, (st.step + 1) // spe,
                                  new_ef), loss

            return jax.lax.scan(body, state, None, length=length)

        return jax.jit(chunk, donate_argnums=(0,))

    # -- the driver loop -----------------------------------------------------

    def run(self, state: TrainState, graph, *,
            report: Optional[Callable[[int, float, Optional[float]], None]]
            = None) -> Tuple[TrainState, RunLog]:
        """Run from ``state.step`` to the configured length (or the target
        accuracy) in scan chunks. ``report(step, last_loss, acc)`` fires
        once per eval boundary — the SAME eval feeds the target check.
        Resume-aware: a restored mid-run state continues its schedule.
        When ``ckpt_dir`` is set the FINAL state is always persisted —
        target-accuracy stops and step counts off the ``ckpt_every``
        boundary included."""
        tr = self.tracer
        base = tr.summary()     # a shared tracer may carry earlier runs;
                                # RunLog timing is the DELTA over this run
        t_run0 = time.perf_counter()
        with tr.span("run"), tr.compiles():
            state, log, steps_run = self._run(state, graph, report)
        # the drain at the end of _run blocked on every chunk, so this wall
        # time covers the full train compute; subtract what blocked the
        # driver for other reasons (eval, sync-ckpt writes, async-ckpt
        # joins, compiles outside those) to get the per-step figure. The
        # async worker's io time ("ckpt_io") runs on its own thread —
        # whatever was NOT re-absorbed as a join wait was overlapped with
        # training.
        wall = time.perf_counter() - t_run0
        spent = {}              # path -> (count, seconds) recorded this run
        for path, v in tr.summary().items():
            b = base.get(path, {"count": 0, "total_s": 0.0})
            if v["count"] != b["count"]:
                spent[path] = (v["count"] - b["count"],
                               v["total_s"] - b["total_s"])

        def delta(name: str, outside=()) -> Tuple[int, float]:
            n = t = 0
            for path, (c, s) in spent.items():
                *parents, leaf = path.split("/")
                if leaf == name and not set(parents) & set(outside):
                    n, t = n + c, t + s
            return n, t

        log.eval_s = delta("eval")[1]
        log.ckpt_overlap_s = max(
            0.0, delta("ckpt_io")[1] - delta("ckpt_wait")[1])
        log.programs, log.compile_s = delta("compile")
        if steps_run > 0:
            blocked = (log.eval_s + delta("ckpt")[1] + delta("ckpt_wait")[1]
                       + delta("compile", ("eval", "ckpt", "ckpt_wait"))[1])
            log.ms_per_step = max(0.0, wall - blocked) * 1e3 / steps_run
        return state, log

    def _run(self, state: TrainState, graph, report
             ) -> Tuple[TrainState, RunLog, int]:
        """``run``'s loop: chunks, evals, saves and the final drain; returns
        the steps it ran besides."""
        loop = self.loop
        total = self.total_steps
        log = RunLog()
        done = int(state.step)
        start_step = done
        # boundaries already behind a resumed state are not re-run
        eval_every = self.eval_every
        eval_mark = done // eval_every if eval_every else 0
        ckpt_mark = done // loop.ckpt_every if loop.ckpt_every else 0
        saved_at = None         # step of the newest (possibly async) save
        device_losses = []      # per-chunk device arrays; materialized once
                                # at the end so chunks keep dispatching async
        tr = self.tracer

        while done < total and not log.hit_target:
            n = min(loop.chunk_size, total - done)
            # dispatch time (chunks run async), annotated with the chunk's
            # first global step
            with tr.span("chunk", step=done):
                state, losses = self.compiled_chunk(n)(state, graph)
            done += n
            device_losses.append(losses)

            if eval_every and done // eval_every > eval_mark:
                eval_mark = done // eval_every
                with tr.span("eval"):
                    acc = float(self.eval_fn(state.params, graph))   # ONCE
                log.evals.append((done, acc))
                if report is not None:
                    report(done, float(losses[-1]), acc)
                if loop.target_acc is not None and acc >= loop.target_acc:
                    log.hit_target = True
            if (loop.ckpt_dir and loop.ckpt_every
                    and done // loop.ckpt_every > ckpt_mark):
                ckpt_mark = done // loop.ckpt_every
                self.save(state, sync=not loop.async_ckpt, step=done)
                saved_at = done

        if loop.ckpt_dir:
            if saved_at == done:
                # the boundary save above already covers the final state;
                # just wait for it and report its path
                self.join_saves()
                log.final_ckpt = checkpoint_path(
                    loop.ckpt_dir, done, name=CKPT_NAME)
            else:
                log.final_ckpt = self.save(state)       # sync: run() exit
        else:
            self.join_saves()                           # surface any error

        with tr.span("drain"):          # the device finishes every chunk
            log.losses = [float(x) for arr in device_losses
                          for x in np.asarray(arr)]
        return state, log, done - start_step
