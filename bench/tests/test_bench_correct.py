"""The comparison that decides ``correct``, driven through a whole run of
the harness on the CPU at a small size (the Pallas kernels interpreted):
a sound run passes; the float8 control in the program's place, and each
fault a one-chip training cell can have, planted in the program, fail.

The limits are the chip cell's own (``bench/limits/products-b1024.json``).
"""
import time

import pytest

from benchkit import tiny_cell

SECONDS = 0.2


def _run(cpu_devices):
    from bench import harness
    return harness.run_cell(tiny_cell(), 2 ** 31 + 11, SECONDS, False,
                            cpu_devices, time.perf_counter())


def test_sound_run_is_correct(cpu_devices):
    r = _run(cpu_devices)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"step_ms", "setup_s"}


def test_fp8_control_in_the_programs_place_is_not_correct(
        cpu_devices, monkeypatch):
    from bench import harness
    start = harness.TrainCell.start

    def control(self, seed):
        state, step, _, params = start(self, seed)
        first = step - 2 * self.chunk
        got = self.reference(params, first, self.ref_graph(), "fp8")
        return state, step, got, params

    monkeypatch.setattr(harness.TrainCell, "start", control)
    assert _run(cpu_devices)["correct"] is False


def _unchanged_state(monkeypatch):
    from repro.optim import AdamW
    monkeypatch.setattr(AdamW, "update",
                        lambda self, params, grads, state: (params, state))


def _half_batch(monkeypatch):
    from repro.core import pmm3d
    full = pmm3d.parallel_cross_entropy

    def half(logits, labels, **kw):
        h = logits.shape[0] // 2
        return full(logits[:h], labels[:h], **kw)

    monkeypatch.setattr(pmm3d, "parallel_cross_entropy", half)


def _altered_loss(monkeypatch):
    from repro.core import pmm3d
    full = pmm3d.parallel_cross_entropy

    def altered(logits, labels, **kw):
        nll, cnt = full(logits, labels, **kw)
        return nll * 1.01, cnt

    monkeypatch.setattr(pmm3d, "parallel_cross_entropy", altered)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _altered_loss],
                         ids=["unchanged_state", "half_batch",
                              "altered_loss"])
def test_fault_in_the_timed_path_is_not_correct(cpu_devices, monkeypatch,
                                                plant):
    plant(monkeypatch)
    assert _run(cpu_devices)["correct"] is False


def test_a_number_without_a_limit_is_reported_not_compared():
    from bench import check
    values = {"loss": 0.5, "loss_held": [0.5, 0.1], "loss_steps": [0.2],
              "grad": 1e-3, "update": 1e-3}
    limits = {"grad": 0.03, "update": 0.01}
    assert check.compared(limits) == ["grad", "update"]
    assert check.verdict(values, limits)
    assert not check.verdict(dict(values, loss_steps=[float("nan")]),
                             limits)
    assert not check.verdict(dict(values, loss_held=[0.5, float("inf")]),
                             limits)
    assert not check.verdict(values, dict(limits, loss=0.1))
    assert [line.split(":")[0] for line in check.report(values, limits)] \
        == ["check grad", "check update"]


def test_held_chunk_holds_the_weights_and_reads_first_gradients():
    """The held chunk's second moment stops every update: the weights end
    as they began, and the first moment is the chunk's gradients at them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import graphgen, manifest, reference
    gcn = manifest.load_model("gcn")
    g = graphgen.generate(256, 4, 8, 6.0, seed=3)
    a = g.adj_norm
    graph = tuple(jnp.asarray(x) for x in (a.indptr, a.indices, a.data,
                                            g.features, g.labels))
    model = gcn.Model(n=256, batch=32, max_row_nnz=g.max_row_nnz,
                      program_seed=0, d_in=8, d_hidden=16, num_layers=2,
                      num_classes=4, dropout=0.3, rms_eps=1e-6)
    params = gcn.init_params(jax.random.PRNGKey(1), model)
    opt = reference.Adam(lr=5e-3)
    held = reference.run(params, gcn.loss_fn, model, opt, graph, 5, 3,
                         nu0=reference.HELD_NU)
    assert all(np.all(d == 0) for d in held.delta)
    grads = [reference.leaves(jax.grad(gcn.loss_fn)(
        params, model, graph, jnp.int32(5 + t))) for t in range(3)]
    mu = [0.1 * (0.81 * a + 0.9 * b + c) for a, b, c in zip(*grads)]
    for got, want in zip(held.mu, mu):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-9)
    both = reference.checked(params, gcn.loss_fn, model, opt, graph, 5, 3)
    assert both.held.losses == held.losses
    assert any(np.any(d != 0) for d in both.trained.delta)


def test_a_program_built_after_set_up_fails_the_run(cpu_devices,
                                                     monkeypatch):
    """The checked chunks and the window run the program set-up built: a
    trainer that builds its chunk anew on every call fails the run."""
    from repro.train import Trainer
    monkeypatch.setattr(Trainer, "compiled_chunk",
                        lambda self, length: self._build_chunk(length))
    with pytest.raises(RuntimeError, match="must run the window's"):
        _run(cpu_devices)
