"""Tests for the scan-chunked training runtime (``repro.train``) and the
unified forward engine (``core/forward.py``) — the ISSUE-4 acceptance
criteria, runnable on one CPU device via the g_d = g = 1 mesh:

* the scan-chunked runner produces the BIT-identical loss sequence to the
  legacy per-step Python loops (prefetch off AND on);
* save mid-run -> restore ``TrainState`` -> the resumed loss sequence and
  final params are bit-identical to an uninterrupted run (the first real
  exercise of ``load_checkpoint`` on the train path), prefetch on and off;
* one eval per report boundary feeds BOTH the report and the
  target-accuracy stop (the legacy double-eval is structurally gone);
* the §V-C fused elementwise tail (``TrainOptions.fused_elementwise``,
  routed through the engine's tail hook) agrees with the unfused
  reference — forward exactly, gradients to float tolerance.
"""
import os
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fourd, gcn_model as M, pipeline as PL
from repro.graphs import build_partitioned_graph, make_synthetic_dataset
from repro.optim import AdamW
from repro.train import Trainer, TrainLoopConfig, TrainState
from repro.train import runner as runner_mod

STEPS = 6


@pytest.fixture(scope="module")
def setup():
    ds = make_synthetic_dataset(n=256, num_classes=4, d_in=16,
                                avg_degree=8, seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    plan = fourd.build_plan(pg, cfg, mesh, batch=64,
                            opts=fourd.TrainOptions(dropout=0.2))
    graph = plan.shard_graph(pg)
    return pg, cfg, mesh, plan, graph


@pytest.fixture()
def fresh_params(setup):
    """A params *factory*: chunk buffers are donated, so every run needs its
    own copy of the initial parameters."""
    _, cfg, _, plan, _ = setup
    return lambda: plan.shard_params(
        M.init_params(jax.random.PRNGKey(1), cfg))


def _per_step_losses(plan, graph, params, opt, prefetch: bool):
    """The legacy per-step Python loops (the bit-identity reference)."""
    losses = []
    if prefetch:
        sample_fn, step_fn = PL.make_prefetched_train_step(plan, opt)
        state = PL.PrefetchState(params, opt.init(params),
                                 sample_fn(graph, jnp.asarray(0)))
        for s in range(STEPS):
            state, loss = step_fn(state, graph, jnp.asarray(s))
            losses.append(float(loss))
    else:
        ts = fourd.make_train_step(plan, opt)
        p, o = params, opt.init(params)
        for s in range(STEPS):
            p, o, loss = ts(p, o, graph, jnp.asarray(s))
            losses.append(float(loss))
    return losses


@pytest.mark.parametrize("prefetch", [False, True])
@pytest.mark.parametrize("chunk", [1, 4])
def test_scan_chunked_bitmatches_per_step_loop(setup, fresh_params,
                                               prefetch, chunk):
    """Acceptance: chunked scan == per-step loop, bit for bit, for chunk
    sizes that do and don't divide the step count (1, 4 over 6 steps)."""
    _, _, _, plan, graph = setup
    opt = AdamW(lr=5e-3)
    ref = _per_step_losses(plan, graph, fresh_params(), opt, prefetch)
    tr = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=chunk, prefetch=prefetch))
    state, log = tr.run(tr.init_state(fresh_params(), graph), graph)
    assert log.losses == ref                     # bit-identical floats
    assert int(state.step) == STEPS


@pytest.mark.parametrize("prefetch", [False, True])
def test_checkpoint_resume_bitmatches_uninterrupted(setup, fresh_params,
                                                    tmp_path, prefetch):
    """Save mid-run, restore into a FRESH Trainer, and continue: the
    resumed loss tail and the final params must be bit-identical to the
    uninterrupted run."""
    _, _, _, plan, graph = setup
    opt = AdamW(lr=5e-3)
    loop = TrainLoopConfig(total_steps=STEPS, chunk_size=2,
                           prefetch=prefetch, ckpt_dir=str(tmp_path),
                           ckpt_every=4)
    full_state, full_log = Trainer(plan, opt, loop).run(
        Trainer(plan, opt, loop).init_state(fresh_params(), graph), graph)

    resumed = Trainer(plan, opt, loop)           # no shared jit caches
    example = resumed.init_state(fresh_params(), graph)
    state = resumed.restore(example, step=4)
    assert isinstance(state, TrainState) and int(state.step) == 4
    state, log = resumed.run(state, graph)

    assert log.losses == full_log.losses[4:]     # bit-identical tail
    for a, b in zip(jax.tree.leaves(full_state.params),
                    jax.tree.leaves(state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(full_state.opt_state),
                    jax.tree.leaves(state.opt_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_restore_none_when_no_checkpoint(setup, fresh_params, tmp_path):
    _, _, _, plan, graph = setup
    opt = AdamW(lr=5e-3)
    tr = Trainer(plan, opt, TrainLoopConfig(
        total_steps=2, ckpt_dir=str(tmp_path)))
    assert tr.restore(tr.init_state(fresh_params(), graph)) is None


def test_eval_runs_once_per_report_boundary(setup, fresh_params):
    """The legacy loop evaluated twice per report step (_maybe_report +
    _reached_target). The runtime evaluates ONCE per boundary and reuses
    it for the target check."""
    _, _, _, plan, graph = setup
    opt = AdamW(lr=5e-3)
    real_eval = fourd.make_eval_step(plan)
    calls = []

    def counting_eval(params, g):
        calls.append(1)
        return real_eval(params, g)

    tr = Trainer(plan, opt,
                 TrainLoopConfig(total_steps=STEPS, chunk_size=2,
                                 eval_every=2, target_acc=2.0),
                 eval_fn=counting_eval)
    _, log = tr.run(tr.init_state(fresh_params(), graph), graph)
    assert len(calls) == STEPS // 2              # one per boundary: 2, 4, 6
    assert [s for s, _ in log.evals] == [2, 4, 6]
    assert not log.hit_target

    # an immediately-satisfied target stops after exactly ONE eval
    calls.clear()
    tr2 = Trainer(plan, opt,
                  TrainLoopConfig(total_steps=STEPS, chunk_size=2,
                                  eval_every=2, target_acc=0.0),
                  eval_fn=counting_eval)
    state, log2 = tr2.run(tr2.init_state(fresh_params(), graph), graph)
    assert len(calls) == 1 and log2.hit_target
    assert int(state.step) == 2                  # stopped at the boundary


# ---------------------------------------------------------------------------
# Multi-epoch without-replacement schedule (ISSUE-5 tentpole)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def epoch_setup(setup):
    """The same graph under the without-replacement schedule: n_pad = 256,
    batch = 64 -> 4 steps per epoch."""
    pg, cfg, mesh, _, _ = setup
    plan = fourd.build_plan(
        pg, cfg, mesh, batch=64,
        opts=fourd.TrainOptions(dropout=0.2, sample_mode="epoch"))
    return plan, plan.shard_graph(pg)


@pytest.fixture()
def epoch_params(setup, epoch_setup):
    _, cfg, _, _, _ = setup
    plan, _ = epoch_setup
    return lambda: plan.shard_params(
        M.init_params(jax.random.PRNGKey(1), cfg))


def test_epoch_prefetch_crosses_boundary_bit_identical(epoch_setup,
                                                       epoch_params):
    """Tentpole acceptance: with chunk 3 over 2 epochs of 4 steps, the
    §V-A prefetch carry crosses the epoch boundary INSIDE a scan chunk
    (steps 3->4 live in the chunk covering steps 3-5) and the loss
    sequence is bit-identical to prefetch-off."""
    plan, graph = epoch_setup
    opt = AdamW(lr=5e-3)
    out = {}
    for prefetch in (False, True):
        tr = Trainer(plan, opt, TrainLoopConfig(
            epochs=2, chunk_size=3, prefetch=prefetch))
        assert tr.total_steps == 8 and tr.steps_per_epoch == 4
        state, log = tr.run(tr.init_state(epoch_params(), graph), graph)
        assert int(state.step) == 8 and int(state.epoch) == 2
        out[prefetch] = log.losses
    assert out[True] == out[False]               # bit-identical floats


def test_epoch_schedule_changes_the_sample_stream(epoch_setup, setup,
                                                  epoch_params,
                                                  fresh_params):
    """The without-replacement schedule is a different (deterministic)
    sample stream from the per-step one — and re-running it reproduces
    itself exactly."""
    pg, cfg, mesh, plan_step, graph_step = setup
    plan_e, graph_e = epoch_setup
    opt = AdamW(lr=5e-3)

    def losses(plan, graph, params):
        tr = Trainer(plan, opt, TrainLoopConfig(total_steps=4,
                                                chunk_size=2))
        return tr.run(tr.init_state(params, graph), graph)[1].losses

    a = losses(plan_e, graph_e, epoch_params())
    b = losses(plan_e, graph_e, epoch_params())
    c = losses(plan_step, graph_step, fresh_params())
    assert a == b
    assert a != c


def test_mid_epoch_resume_bit_identical(epoch_setup, epoch_params,
                                        tmp_path):
    """Save at step 3 of a 4-step epoch (mid-epoch), restore into a fresh
    Trainer, continue across the boundary: tail and final state must be
    bit-identical to the uninterrupted 2-epoch run."""
    plan, graph = epoch_setup
    opt = AdamW(lr=5e-3)
    loop = TrainLoopConfig(epochs=2, chunk_size=3, ckpt_dir=str(tmp_path),
                           ckpt_every=3)
    full_state, full_log = Trainer(plan, opt, loop).run(
        Trainer(plan, opt, loop).init_state(epoch_params(), graph), graph)

    resumed = Trainer(plan, opt, loop)
    state = resumed.restore(resumed.init_state(epoch_params(), graph),
                            step=3)
    assert int(state.step) == 3 and int(state.epoch) == 0
    state, log = resumed.run(state, graph)
    assert int(state.epoch) == 2
    assert log.losses == full_log.losses[3:]
    for a, b in zip(jax.tree.leaves(full_state.params),
                    jax.tree.leaves(state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Async checkpointing (ISSUE-5 tentpole) + final-state save (satellite)
# ---------------------------------------------------------------------------

def test_async_save_byte_identical_to_sync(setup, fresh_params, tmp_path):
    plan = setup[3]
    graph = setup[4]
    opt = AdamW(lr=5e-3)
    tr = Trainer(plan, opt, TrainLoopConfig(total_steps=2))
    state = tr.init_state(fresh_params(), graph)
    d_sync, d_async = str(tmp_path / "sync"), str(tmp_path / "async")
    p = tr.save(state, d_sync)
    assert tr.save(state, d_async, sync=False, step=0) is None
    tr.join_saves()
    with open(p, "rb") as f:
        sync_bytes = f.read()
    with open(os.path.join(d_async, os.path.basename(p)), "rb") as f:
        async_bytes = f.read()
    assert sync_bytes == async_bytes


def test_async_save_survives_donation_of_the_live_state(setup, fresh_params,
                                                        tmp_path):
    """The snapshot must be fetched from FRESH buffers: dispatching the
    next (donating) chunk right after an async save must not corrupt or
    invalidate the bytes being written."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    tr = Trainer(plan, opt, TrainLoopConfig(total_steps=4, chunk_size=2))
    state = tr.init_state(fresh_params(), graph)
    ref = jax.device_get(state)
    tr.save(state, str(tmp_path), sync=False, step=0)
    tr.compiled_chunk(2)(state, graph)           # donates state's buffers
    tr.join_saves()
    got = tr.restore(ref, str(tmp_path), step=0)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_async_save_overlaps_and_join_reraises(setup, fresh_params,
                                               tmp_path, monkeypatch):
    """save(sync=False) returns while the write is still in flight (the
    overlap), join_saves() waits for it, and a writer failure surfaces at
    the join instead of disappearing on the worker thread."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    tr = Trainer(plan, opt, TrainLoopConfig(total_steps=2))
    state = tr.init_state(fresh_params(), graph)

    started, release = threading.Event(), threading.Event()
    real = runner_mod.save_checkpoint

    def gated(directory, step, tree, name="ckpt"):
        started.set()
        assert release.wait(10)
        return real(directory, step, tree, name=name)

    monkeypatch.setattr(runner_mod, "save_checkpoint", gated)
    tr.save(state, str(tmp_path), sync=False, step=0)
    assert started.wait(10)
    assert tr._save_thread is not None           # still in flight: overlap
    release.set()
    tr.join_saves()
    assert os.path.exists(
        os.path.join(str(tmp_path), "state_00000000.npz"))

    def boom(directory, step, tree, name="ckpt"):
        raise IOError("disk full")

    monkeypatch.setattr(runner_mod, "save_checkpoint", boom)
    tr.save(state, str(tmp_path), sync=False, step=1)
    with pytest.raises(IOError, match="disk full"):
        tr.join_saves()


def test_run_never_blocks_driver_on_device_get(setup, fresh_params,
                                               tmp_path, monkeypatch):
    """Acceptance: with async_ckpt on, every host fetch of checkpoint data
    happens OFF the driver thread (the final boundary save included — the
    run ends exactly on a ckpt_every boundary here)."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    fetch_threads = []
    real = runner_mod._device_get

    def spy(tree):
        fetch_threads.append(threading.get_ident())
        return real(tree)

    monkeypatch.setattr(runner_mod, "_device_get", spy)
    tr = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, ckpt_dir=str(tmp_path),
        ckpt_every=2))
    _, log = tr.run(tr.init_state(fresh_params(), graph), graph)
    assert fetch_threads, "no checkpoint fetch happened at all"
    assert threading.get_ident() not in fetch_threads
    assert log.final_ckpt and os.path.exists(log.final_ckpt)


def test_run_persists_final_state(setup, fresh_params, tmp_path):
    """Satellite: run() itself saves the final state — total_steps off the
    ckpt_every boundary AND target-accuracy early stops both persist,
    without launch/train.py's (deleted) boundary arithmetic."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    d1 = str(tmp_path / "off-boundary")
    tr = Trainer(plan, opt, TrainLoopConfig(
        total_steps=5, chunk_size=2, ckpt_dir=d1, ckpt_every=4))
    state, log = tr.run(tr.init_state(fresh_params(), graph), graph)
    assert int(state.step) == 5
    assert log.final_ckpt.endswith("state_00000005.npz")
    assert os.path.exists(log.final_ckpt)
    assert os.path.exists(os.path.join(d1, "state_00000004.npz"))

    d2 = str(tmp_path / "target-stop")
    tr2 = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, eval_every=2, target_acc=0.0,
        ckpt_dir=d2))
    state2, log2 = tr2.run(tr2.init_state(fresh_params(), graph), graph)
    assert log2.hit_target and int(state2.step) == 2
    assert log2.final_ckpt.endswith("state_00000002.npz")
    assert os.path.exists(log2.final_ckpt)

    # restore-from-final continues without re-running anything
    tr3 = Trainer(plan, opt, TrainLoopConfig(
        total_steps=5, chunk_size=2, ckpt_dir=d1))
    st = tr3.restore(tr3.init_state(fresh_params(), graph))
    assert int(st.step) == 5
    st, log3 = tr3.run(st, graph)
    assert log3.losses == [] and int(st.step) == 5


# ---------------------------------------------------------------------------
# Prefetch-flag mismatch on restore (satellite)
# ---------------------------------------------------------------------------

def test_restore_prefetch_from_plain_ckpt_rebuilds_warmup(setup,
                                                          fresh_params,
                                                          tmp_path):
    """Resuming WITH --prefetch from a checkpoint written without it used
    to die with a raw KeyError; now it either rebuilds the warm-up batch
    (graph given — continuation bit-identical to an all-prefetch run) or
    fails with an actionable message."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    d = str(tmp_path)
    off = Trainer(plan, opt, TrainLoopConfig(
        total_steps=4, chunk_size=2, ckpt_dir=d))
    off.run(off.init_state(fresh_params(), graph), graph)

    on = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, prefetch=True, ckpt_dir=d))
    example = on.init_state(fresh_params(), graph)
    with pytest.raises(ValueError, match="prefetch"):
        on.restore(example)                      # no graph -> actionable
    state = on.restore(example, graph=graph)
    assert int(state.step) == 4 and state.minibatch is not None
    state, log = on.run(state, graph)

    ref = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, prefetch=True))
    _, ref_log = ref.run(ref.init_state(fresh_params(), graph), graph)
    assert log.losses == ref_log.losses[4:]      # bit-identical tail


def test_restore_pre_epoch_ckpt_backfills_counter(epoch_setup, epoch_params,
                                                  tmp_path):
    """A PR-4-layout checkpoint (no ``.epoch`` leaf) must still resume:
    the counter is derivable from the step, so restore backfills it
    instead of dying on the missing leaf."""
    import dataclasses as dc
    plan, graph = epoch_setup
    opt = AdamW(lr=5e-3)
    loop = TrainLoopConfig(epochs=2, chunk_size=3, ckpt_dir=str(tmp_path))
    tr = Trainer(plan, opt, loop)
    tr.run(tr.init_state(epoch_params(), graph), graph)

    # rewrite a mid-epoch-1 state in the OLD layout: epoch leaf stripped
    mid = Trainer(plan, opt, loop)
    st8 = mid.restore(mid.init_state(epoch_params(), graph), step=8)
    old = dc.replace(st8, step=np.asarray(6, np.int32), epoch=None)
    runner_mod.save_checkpoint(str(tmp_path), 6, old,
                               name=runner_mod.CKPT_NAME)

    resumed = Trainer(plan, opt, loop)
    state = resumed.restore(resumed.init_state(epoch_params(), graph),
                            step=6)
    assert int(state.step) == 6 and int(state.epoch) == 1   # backfilled


def test_cli_rejects_steps_with_epochs():
    from repro.launch import train as cli
    with pytest.raises(SystemExit, match="mutually exclusive"):
        cli.main(["--steps", "4", "--epochs", "1"])


def test_runlog_tracer_timing_fields(setup, fresh_params, tmp_path):
    """Observability satellite: a traced run populates the RunLog timing
    fields from the tracer — ms_per_step excludes the eval/blocking-ckpt
    time, eval_s covers the boundary evals, and the async checkpoint's io
    time is recorded so the hidden fraction is derivable."""
    from repro.obs import Tracer

    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    tr = Tracer(enabled=True)
    trainer = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, eval_every=3,
        ckpt_dir=str(tmp_path), ckpt_every=3), tracer=tr)
    _, log = trainer.run(trainer.init_state(fresh_params(), graph), graph)

    assert log.ms_per_step > 0.0
    assert log.eval_s > 0.0            # two boundary evals ran
    assert log.ckpt_overlap_s >= 0.0
    s = tr.summary()
    assert s["run"]["count"] == 1
    assert s["run/chunk"]["count"] == STEPS // 2
    assert s["run/eval"]["count"] == len(log.evals)
    assert s["run/drain"]["count"] == 1
    assert tr.total("ckpt_io") > 0.0   # the async worker reported its time

    # a disabled tracer must not sabotage the run — timing fields just
    # degrade (eval time can no longer be subtracted out)
    off = Trainer(plan, opt, TrainLoopConfig(total_steps=STEPS,
                                             chunk_size=2),
                  tracer=Tracer(enabled=False))
    _, log_off = off.run(off.init_state(fresh_params(), graph), graph)
    assert log_off.ms_per_step > 0.0 and log_off.eval_s == 0.0


def test_runlog_compile_accounting(setup, fresh_params):
    """The programs a run builds are counted and their build time (trace,
    lowering, XLA compile) leaves ms_per_step, as eval time does: the
    first run builds the chunk, a second run of the same length builds
    nothing."""
    import time

    plan, graph = setup[3], setup[4]
    trainer = Trainer(plan, AdamW(lr=5e-3), TrainLoopConfig(
        total_steps=STEPS, chunk_size=2))
    state = trainer.init_state(fresh_params(), graph)
    t0 = time.perf_counter()
    state, first = trainer.run(state, graph)
    wall = time.perf_counter() - t0
    assert first.programs >= 1 and first.compile_s > 0.0
    # what is left after the compile is less than the compile itself
    train_s = first.ms_per_step * STEPS / 1e3
    assert 0.0 < train_s <= wall - first.compile_s
    assert train_s < first.compile_s

    trainer.total_steps = 2 * STEPS
    _, second = trainer.run(state, graph)
    assert second.programs == 0 and second.compile_s == 0.0
    assert second.ms_per_step > 0.0
    assert trainer.tracer.summary()["run/chunk/compile"]["count"] \
        == first.programs


def _host_events(trace_dir, outer=None):
    """``(name, start_ns, end_ns, stats)`` of the ``repro.*`` events (and
    of those called ``outer``) on the host plane of the profile written
    under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.") or ev.name == outer]


def test_run_spans_reach_the_profiler_trace(setup, fresh_params, tmp_path):
    """Under ``jax.profiler.trace`` an enabled Trainer puts its host spans
    on the host plane, inside the caller's annotation: ``repro.run``
    around the call, one ``repro.chunk`` step annotation per chunk
    carrying the chunk's first global step, and ``repro.drain``."""
    plan, graph = setup[3], setup[4]
    trainer = Trainer(plan, AdamW(lr=5e-3), TrainLoopConfig(
        total_steps=STEPS, chunk_size=2))
    state, _ = trainer.run(trainer.init_state(fresh_params(), graph), graph)
    trainer.total_steps = 2 * STEPS
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            trainer.run(state, graph)
    events = _host_events(str(tmp_path), "caller")
    by = lambda name: [e for e in events if e[0] == name]
    ((_, c0, c1, _),) = by("caller")
    assert len(by("repro.run")) == 1 and len(by("repro.drain")) == 1
    chunks = by("repro.chunk")
    assert [e[3]["step_num"] for e in chunks] == list(range(STEPS,
                                                           2 * STEPS, 2))
    for _, s, e, _ in by("repro.run") + by("repro.drain") + chunks:
        assert c0 <= s <= e <= c1


def test_cli_trace_dir_holds_the_runtime_spans(tmp_path):
    """``launch/train.py --trace-dir`` writes a profile holding the
    Trainer's ``repro.*`` spans."""
    from repro.launch import train as cli

    cli.main(["--dataset", "ogbn-products", "--vertices", "256",
              "--gd", "1", "--g", "1", "--batch", "64",
              "--d-hidden", "32", "--layers", "2", "--steps", "4",
              "--chunk-size", "2", "--trace-dir", str(tmp_path)])
    names = [e[0] for e in _host_events(str(tmp_path))]
    assert names.count("repro.run") == 1 and names.count("repro.drain") == 1
    assert names.count("repro.chunk") == 2


def test_cli_metrics_json_dump(tmp_path, capsys):
    """--metrics-json writes the scripted-run artifact: run config, the
    full RunLog (losses + tracer-derived timing), and the span summary."""
    import json

    from repro.launch import train as cli
    from repro.obs import get_tracer, set_tracer

    path = tmp_path / "metrics.json"
    prev = get_tracer()
    try:
        cli.main(["--dataset", "ogbn-products", "--vertices", "256",
                  "--gd", "1", "--g", "1", "--batch", "64",
                  "--d-hidden", "32", "--layers", "2", "--steps", "4",
                  "--chunk-size", "2", "--eval-every", "2",
                  "--metrics-json", str(path)])
        assert get_tracer() is prev    # the CLI keeps a tracer of its own
    finally:
        set_tracer(prev)
    doc = json.loads(path.read_text())
    assert doc["run"]["steps"] == 4 and doc["run"]["batch"] == 64
    assert 0.0 <= doc["run"]["final_acc"] <= 1.0
    assert len(doc["runlog"]["losses"]) == 4
    assert doc["runlog"]["ms_per_step"] > 0.0
    assert doc["runlog"]["eval_s"] > 0.0
    assert doc["spans"]["run/chunk"]["count"] == 2
    assert "run/eval" in doc["spans"]
    # phase() opens no host span: every recorded span is a runtime span
    assert {p.rsplit("/", 1)[-1] for p in doc["spans"]} <= {
        "run", "chunk", "drain", "eval", "ckpt", "ckpt_wait", "ckpt_io",
        "compile"}
    out = capsys.readouterr().out
    assert "ms/step" in out and f"metrics: {path}" in out


def test_restore_plain_from_prefetch_ckpt_drops_carry(setup, fresh_params,
                                                      tmp_path):
    """The reverse direction: the saved carry is redundant (a pure function
    of (seed, epoch, step)) and is dropped deliberately — the continuation
    still bit-matches the uninterrupted non-prefetch run."""
    plan, graph = setup[3], setup[4]
    opt = AdamW(lr=5e-3)
    d = str(tmp_path)
    on = Trainer(plan, opt, TrainLoopConfig(
        total_steps=4, chunk_size=2, prefetch=True, ckpt_dir=d))
    on.run(on.init_state(fresh_params(), graph), graph)

    off = Trainer(plan, opt, TrainLoopConfig(
        total_steps=STEPS, chunk_size=2, ckpt_dir=d))
    state = off.restore(off.init_state(fresh_params(), graph))
    assert int(state.step) == 4 and state.minibatch is None
    state, log = off.run(state, graph)

    ref = Trainer(plan, opt, TrainLoopConfig(total_steps=STEPS,
                                             chunk_size=2))
    _, ref_log = ref.run(ref.init_state(fresh_params(), graph), graph)
    assert log.losses == ref_log.losses[4:]


@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_fused_elementwise_matches_reference(setup, fresh_params, dropout):
    """Satellite: the §V-C fused Pallas tail (engine tail hook) is no
    longer a dead flag — and it must not change the math. At g = 1 the
    fully-fused path (RMSNorm owned by the kernel) is exercised."""
    pg, cfg, mesh, _, graph = setup
    plan0 = fourd.build_plan(pg, cfg, mesh, batch=64,
                             opts=fourd.TrainOptions(dropout=dropout))
    plan1 = fourd.build_plan(
        pg, cfg, mesh, batch=64,
        opts=fourd.TrainOptions(dropout=dropout, fused_elementwise=True))
    params = fresh_params()
    for train in (False, True):
        l0 = np.array(jax.jit(fourd.make_loss_fn(plan0, train=train))(
            params, graph, jnp.asarray(3)))
        l1 = np.array(jax.jit(fourd.make_loss_fn(plan1, train=train))(
            params, graph, jnp.asarray(3)))
        np.testing.assert_allclose(l1, l0, rtol=1e-6)

    def mean_loss(plan):
        return lambda p: fourd.make_loss_fn(plan, train=True)(
            p, graph, jnp.asarray(0)).mean()

    g0 = jax.jit(jax.grad(mean_loss(plan0)))(params)
    g1 = jax.jit(jax.grad(mean_loss(plan1)))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.array(b), np.array(a), atol=1e-6)


def test_eval_step_csr_backend_matches_reference_forward(setup,
                                                         fresh_params):
    """The engine's "csr" backend (full-graph eval) reproduces the
    single-device reference model's accuracy on the whole graph."""
    pg, cfg, mesh, plan, graph = setup
    params = fresh_params()
    acc_4d = float(fourd.make_eval_step(plan)(params, graph))
    dense = jnp.array(csr_to_dense_padded(pg))
    logits = M.forward(M.init_params(jax.random.PRNGKey(1), cfg), dense,
                       jnp.array(pg.features), cfg, train=False)
    acc_ref = float(M.accuracy(logits, jnp.array(pg.labels),
                               jnp.array(pg.labels >= 0)))
    assert abs(acc_4d - acc_ref) < 1e-6


def csr_to_dense_padded(pg):
    """Densify the g=1 padded-CSR block (the whole graph) for the oracle."""
    import numpy as _np
    rp = _np.asarray(pg.block_rp)[0, 0]
    ci = _np.asarray(pg.block_ci)[0, 0]
    val = _np.asarray(pg.block_val)[0, 0]
    n = pg.n_pad
    out = _np.zeros((n, n), _np.float32)
    for r in range(n):
        for k in range(rp[r], rp[r + 1]):
            if ci[k] < n:
                out[r, ci[k]] += val[k]
    return out
