"""One training cell: the program's own trainer on a seeded graph, driven
through ``repro.train.Trainer.run`` for a measured window, its first two
chunks checked against the plain reference. What is particular to the
model comes from its kind's module (``Cell.kind``, ``bench/models/``):
the program's model configuration, the weights, the reference's layers and
the work a step requires.

Set-up builds one trainer and warms up the one compiled chunk the window
runs: a throwaway state goes through two chunks, the second of which takes
its state in the layout every later chunk takes and gives back. A seed
then gives the weights and the global step the run starts at, so every
seed draws other samples and dropout masks through the same compiled
program (the program's own seed is fixed by the configuration: it is a
constant of the compiled step). Two checked chunks of the seed's state go
through that program (``reference.Checked``): one with the weights held
still, one that trains; the window goes on from the second, repeating
``Trainer.run`` calls of ``CHUNKS_PER_CALL`` chunks until ``seconds`` have
passed. No program is built from the first checked chunk to the window's
end; a run that builds one fails.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional

import numpy as np

from bench import check, graphgen, manifest, reference, trace, work

# sampled-block counts are read for this many traced steps at a time
COUNT_BATCH = 16
# scan chunks per Trainer.run call in the window
CHUNKS_PER_CALL = 4


class CompileClock:
    """Seconds spent compiling (or reading a compiled program back from the
    persistent cache), the number of persistent-cache hits, and the number
    of programs built (lowered, whether then compiled or read from the
    cache), from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.programs += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def open_chips(cell: manifest.Cell, cache_dir: str):
    """Turn on JAX's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``cache_dir``) for every program, and return the
    cell's chips; None where JAX finds no TPU or fewer chips than the cell
    asks for."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {devices}")
        return None
    return devices[:cell.chips]


def seed_parts(seed: int):
    """(weights seed, first global step) drawn from the run's seed."""
    rng = np.random.default_rng(seed)
    return int(rng.integers(2 ** 31)), int(rng.integers(2 ** 30))


class TrainCell:
    def __init__(self, cell: manifest.Cell, devices):
        c, t = cell.config, cell.traffic
        assert t["sampler"] == "stratified" and t["schedule"] == "step", (
            "the reference follows the stratified per-step sampler only")
        self.cell = cell
        self.devices = devices
        self.gcfg = c["graph"]
        self.mcfg = c["model"]
        self.tcfg = c["train"]
        self.opts = c["options"]
        self.kind = cell.kind
        self.batch = int(t["batch"])
        self.chunk = int(t["steps_per_chunk"])
        self.per_call = self.chunk * CHUNKS_PER_CALL
        self.clock = CompileClock()
        self.layout = None

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        from repro.core import fourd
        from repro.graphs import build_partitioned_graph
        from repro.optim import AdamW
        from repro.train import Trainer, TrainLoopConfig

        g, m = self.gcfg, self.mcfg
        t0 = time.perf_counter()
        self.graph = graphgen.generate(
            int(g["vertices"]), int(g["num_classes"]), int(g["feature_dim"]),
            float(g["asked_mean_degree"]), seed=int(g["graph_seed"]),
            p_in_out_ratio=float(g["p_in_out_ratio"]),
            feature_noise=float(g["feature_noise"]))
        log(f"graph: {self.graph.adj_norm.n_rows} vertices, "
            f"{self.graph.adj_norm.nnz} entries, mean degree "
            f"{self.graph.mean_degree:.3f}, max row "
            f"{self.graph.max_row_nnz} ({time.perf_counter() - t0:.2f} s)")
        t1 = time.perf_counter()
        pg = build_partitioned_graph(self.graph, g=1)
        sizes = reference.Sizes(
            n=self.graph.adj_norm.n_rows, batch=self.batch,
            max_row_nnz=self.graph.max_row_nnz,
            program_seed=int(self.tcfg["program_seed"]))
        model = self.model = self.kind.build(m, sizes, pg.feature_dim,
                                             pg.num_classes)
        opts = fourd.TrainOptions(
            seed=sizes.program_seed, sample_kind="stratified",
            sample_mode="step", **self.opts,
            **self.kind.train_options(model))
        self.plan = fourd.build_plan(
            pg, self.kind.program_config(model),
            fourd.make_mesh_4d(1, 1, np.array(self.devices[:1])),
            self.batch, opts=opts)
        self.graph_dev = self.plan.shard_graph(pg)
        del pg
        self.trainer = Trainer(
            self.plan, AdamW(lr=float(self.tcfg["lr"])),
            TrainLoopConfig(total_steps=self.chunk, chunk_size=self.chunk,
                            prefetch=bool(self.cell.traffic["prefetch"])))
        self.init = jax.jit(lambda k: self.kind.init_params(k, model))
        log(f"plan and sharding: {time.perf_counter() - t1:.2f} s")
        self.warm_up()

    def fresh(self, params, step: int, nu0: float = 0.0):
        """A fresh training state from a copy of ``params`` at global step
        ``step``, Adam's second moment started at ``nu0``, in the layout
        the window's chunk takes once set-up has found it. The chunk
        donates its state, so no two states share a buffer."""
        import jax
        import jax.numpy as jnp
        params = jax.tree.map(jnp.copy, params)
        state = self.trainer.init_state(self.plan.shard_params(params),
                                        self.graph_dev)
        opt = dict(state.opt_state)
        if nu0:
            opt["nu"] = jax.tree.map(lambda x: jnp.full_like(x, nu0),
                                     opt["nu"])
        spe = self.plan.scfg.steps_per_epoch
        state = dataclasses.replace(
            state, opt_state=opt, step=jnp.asarray(step, jnp.int32),
            epoch=jnp.asarray(step // spe, jnp.int32))
        if self.layout is not None:
            state = jax.device_put(state, self.layout)
        return state

    def _chunks(self, state, steps: int):
        """``Trainer.run`` from ``state`` for ``steps`` steps."""
        self.trainer.total_steps = int(state.step) + steps
        return self.trainer.run(state, self.graph_dev)

    def warm_up(self) -> None:
        """Build the window's chunk on a throwaway state. The first chunk
        takes the state as ``shard_params`` lays it out and gives it back
        in another layout, so a second chunk compiles; that second one
        gives back the layout it takes, and is the program every checked
        chunk and the window run."""
        import jax
        t0 = time.perf_counter()
        state = self.fresh(self.init(jax.random.PRNGKey(0)), 0)
        state, _ = self._chunks(state, self.chunk)
        layout = jax.tree.map(lambda x: x.sharding, state)
        state, _ = self._chunks(state, self.chunk)
        after = jax.tree.map(lambda x: x.sharding, state)
        if jax.tree.leaves(after) != jax.tree.leaves(layout):
            raise RuntimeError("the chunk gives back another layout than it "
                               "takes: the window would compile")
        self.layout = layout
        log(f"warm-up: {time.perf_counter() - t0:.2f} s, "
            f"{self.clock.programs} programs built")

    def start(self, seed: int):
        """The seed's two checked chunks through the window's program: the
        held chunk at global steps ``[first, first + chunk)`` and the
        trained chunk after it. Returns (the trained state, the next step,
        the program's ``reference.Checked``, the initial parameters on the
        host)."""
        import jax
        w_seed, first = seed_parts(seed)
        params = self.init(jax.random.PRNGKey(w_seed))
        host_params = jax.device_get(params)
        p0 = reference.leaves(host_params)
        held = self.fresh(params, first, reference.HELD_NU)
        state = self.fresh(params, first + self.chunk)
        built = self.clock.programs
        held, held_log = self._chunks(held, self.chunk)
        state, runlog = self._chunks(state, self.chunk)
        self.expect_no_programs(built, "the checked chunks")

        def run(st, lg):
            return reference.Run(
                losses=list(lg.losses),
                mu=reference.leaves(st.opt_state["mu"]),
                delta=[a - b for a, b in
                       zip(reference.leaves(st.params), p0)],
                grad0=None)

        got = reference.Checked(held=run(held, held_log),
                                trained=run(state, runlog))
        return state, first + 2 * self.chunk, got, host_params

    def expect_no_programs(self, before: int, what: str) -> None:
        if self.clock.programs != before:
            raise RuntimeError(f"{what} built {self.clock.programs - before}"
                               " program(s): they must run the window's "
                               "compiled chunk")

    # -- the window ----------------------------------------------------------

    def window(self, state, step: int, seconds: float,
               trace_dir: Optional[str] = None) -> Dict[str, Any]:
        """``Trainer.run`` calls of ``per_call`` steps until ``seconds``
        have passed. Host spans (``bench.window``, ``bench.run`` and, when
        traced, ``bench.dispatch`` around each chunk's dispatch) go into
        the profiler's trace."""
        import jax
        tr = self.trainer
        if trace_dir is not None:
            chunk_of = tr.compiled_chunk

            def annotated(length):
                fn = chunk_of(length)

                def call(*args):
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        return fn(*args)
                return call

            tr.compiled_chunk = annotated
            jax.profiler.start_trace(trace_dir)
        first, steps, failed = step, 0, 0
        pauses, gc_t0 = [], [0.0]

        def on_gc(phase, info):
            if phase == "start":
                gc_t0[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - gc_t0[0])

        gc.callbacks.append(on_gc)
        built = self.clock.programs
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                step += self.per_call
                tr.total_steps = step
                with jax.profiler.TraceAnnotation("bench.run"):
                    state, runlog = tr.run(state, self.graph_dev)
                steps += len(runlog.losses)
                failed += sum(not math.isfinite(x) for x in runlog.losses)
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        gc.callbacks.remove(on_gc)
        self.expect_no_programs(built, "the window")
        log(f"garbage collections in the window: {len(pauses)}, "
            f"{sum(pauses):.4f} s, longest {max(pauses, default=0.0):.4f} s")
        if trace_dir is not None:
            jax.profiler.stop_trace()
            del tr.compiled_chunk
        return {"state": state, "first": first, "steps": steps,
                "failed": failed, "wall": wall}

    def hlo_text(self, state) -> str:
        """The compiled HLO of the window's chunk (read back from the
        compile cache), whose ``op_name`` metadata names each operation's
        scope."""
        fn = self.trainer.compiled_chunk(self.chunk)
        return fn.lower(state, self.graph_dev).compile().as_text()

    def release(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.graph_dev = self.trainer = self.plan = None
        gc.collect()

    # -- the reference -------------------------------------------------------

    def ref_graph(self):
        import jax.numpy as jnp
        a = self.graph.adj_norm
        return (jnp.asarray(a.indptr), jnp.asarray(a.indices),
                jnp.asarray(a.data), jnp.asarray(self.graph.features),
                jnp.asarray(self.graph.labels))

    def reference(self, params, first: int, graph,
                  variant: str = "f32") -> reference.Checked:
        """The reference's two checked chunks, from global step
        ``first``."""
        return reference.checked(params, self.kind.loss_fn, self.model,
                                 reference.Adam(lr=float(self.tcfg["lr"])),
                                 graph, first, self.chunk, variant)

    def counts(self, graph, first: int, steps: int):
        """Mean sampled-block nonzeros and mean CSR entries of the sampled
        rows over the steps ``[first, first + steps)``."""
        import jax
        import jax.numpy as jnp
        model = self.model
        indptr, indices = graph[0], graph[1]
        fn = jax.jit(jax.vmap(lambda st: reference.block_counts(
            model, indptr, indices, reference.sample(model, st))))
        nnz = rows = 0.0
        for lo in range(0, steps, COUNT_BATCH):
            ids = jnp.arange(COUNT_BATCH, dtype=jnp.int32) + first + lo
            a, b = fn(ids)
            k = min(COUNT_BATCH, steps - lo)
            nnz += float(np.asarray(a)[:k].astype(np.float64).sum())
            rows += float(np.asarray(b)[:k].astype(np.float64).sum())
        return nnz / steps, rows / steps

def run_cell(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
             devices, t_start: float,
             keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run of a cell; returns the result object. ``devices`` are the
    chips the cell uses; ``t_start`` is the process's start on the
    ``perf_counter`` clock."""
    tc = TrainCell(cell, devices)
    clock = tc.clock
    tc.setup()
    state, step, got, params = tc.start(seed)
    first = step - 2 * tc.chunk
    setup_s = time.perf_counter() - t_start
    compiled_in_setup = clock.seconds
    log(f"set-up {setup_s:.3f} s (compile or cache read {clock.seconds:.2f}"
        f" s, {clock.cache_hits} cache hits, {clock.programs} programs)")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        win = tc.window(state, step, seconds, trace_dir)
        log(f"window: {win['steps']} steps in {win['wall']:.3f} s; "
            f"compile seconds in it: {clock.seconds - compiled_in_setup:.3f}")
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in devices if d.memory_stats()]
        device["memory_peak_bytes"] = int(max(peaks)) if peaks else 0
        hlo = tc.hlo_text(win["state"]) if traced else ""
        del state, win["state"]
        tc.release()

        graph = tc.ref_graph()
        ref = tc.reference(params, first, graph)
        values = check.readings(got, ref)
        result: Dict[str, Any] = {"correct": check.verdict(values,
                                                          cell.limits),
                                  "attempted": win["steps"],
                                  "failed": win["failed"]}
        if traced:
            paths = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                     for f in fs if f.endswith(".xplane.pb")]
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                for p in paths:
                    shutil.copy(p, keep_trace)
            events = trace.load(paths[0], trace.scope_paths(hlo))
            red = trace.reduce(events, trace.window_of(events["spans"],
                                                       "bench.window"),
                               cell.scopes)
            nnz, rows = tc.counts(graph, win["first"], win["steps"])
            ctx = {"trace": red, "steps": win["steps"],
                   "work": tc.kind.work(tc.model, nnz, rows),
                   "peak": work.peaks(devices[0].device_kind)}
            log(f"trace: {red['window_s']:.3f} s window, busy "
                f"{red['busy_s']:.3f} s, scopes {red['scope_s']}, "
                f"sampled nnz/step {nnz:.1f}, row entries/step {rows:.1f}")
            metrics = {}
            for m in cell.per_layer:
                v = m.compute(ctx)
                if v is not None:
                    metrics[m.name] = {"value": v, "unit": m.unit}
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["metrics"] = metrics
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        else:
            result["metrics"] = {
                "step_ms": {"value": 1e3 * win["wall"] / win["steps"],
                            "unit": "ms"},
                "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = device
        result["checks"] = {k: {"value": values[k], "limit": cell.limits[k]}
                            for k in check.compared(cell.limits)}
        for line in check.report(values, cell.limits):
            log(line)
        return result
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
