"""The train-while-serve cell's check: a sound run passes; the control in
lower precision, an update that returns its state unchanged, an update
that leaves half of each block out (the mean taken over the rest), a
service that promotes but keeps serving its first state, and an answer
altered where it is produced each fail it.

Each drives a whole run past the harness's look for a chip, on the CPU
(Pallas kernels interpreted), with a promote every 8 blocks.
"""

from __future__ import annotations

from bench.tests.conftest import SMALL_DR

SPEC = {"promote_every": 8, "warmup_s": 0.1}


def _run(cell_run):
    return cell_run("dr_paper.train_while_serve", seconds=0.6, spec=SPEC,
                    config=SMALL_DR)


def test_sound_run_is_correct_and_reports_its_metrics(cell_run):
    out = _run(cell_run)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 16
    assert set(out["metrics"]) == {"tws_rows_per_s", "setup_s"}


def test_control_in_lower_precision_fails(cell_run, monkeypatch):
    from bench.drivers import dr_tws

    real = dr_tws.Driver.check
    monkeypatch.setattr(dr_tws.Driver, "check",
                        lambda self, mode="program": real(self, "control"))
    out = _run(cell_run)
    assert not out["correct"]


def test_an_update_that_returns_its_state_unchanged_fails(cell_run,
                                                          monkeypatch):
    from repro.dr import stages

    monkeypatch.setattr(stages.EASIStage, "update",
                        lambda self, state, x, exe: state)
    out = _run(cell_run)
    assert not out["correct"]
    assert out["checked"]["err_first_state"]["value"] == 1.0


def test_an_update_over_half_of_each_block_fails(cell_run, monkeypatch):
    from repro.kernels import ops

    real = ops.easi_update

    def half(b_mat, h_block, cfg, **kw):
        return real(b_mat, h_block[: h_block.shape[0] // 2], cfg, **kw)

    monkeypatch.setattr(ops, "easi_update", half)
    out = _run(cell_run)
    assert not out["correct"]
    st = out["checked"]["err_first_state"]
    assert st["value"] > st["limit"]


def test_an_answer_altered_where_it_is_produced_fails(cell_run, monkeypatch):
    from repro.dr import model

    real = model.DRModel.transform

    def broken(self, state, x):
        return real(self, state, x).at[5, 1].add(1.0)

    monkeypatch.setattr(model.DRModel, "transform", broken)
    out = _run(cell_run)
    assert not out["correct"]
    assert out["checked"]["err_answers"]["value"] > 0.1


def test_serving_a_stale_state_across_promotes_fails(cell_run, monkeypatch):
    """Promotes go through (the registry holds each new state), but every
    block is answered with the first live state the service saw."""
    from repro.serve import engine

    real = engine.DRService._fused_update_fn
    first = []

    def pinned(self, snap, x):
        fn = real(self, snap, x)

        def call(live, staged, xb):
            if not first:
                first.append(live)
            return fn(first[0], staged, xb)
        return call

    monkeypatch.setattr(engine.DRService, "_fused_update_fn", pinned)
    out = _run(cell_run)
    assert not out["correct"]
    ans = out["checked"]["err_answers"]
    st = out["checked"]["err_first_state"]
    assert ans["value"] > ans["limit"]
    assert st["value"] <= st["limit"]          # the state itself moved right


def test_the_stale_reading_of_a_sound_run_fails(cell_run, monkeypatch):
    from bench.drivers import dr_tws

    real = dr_tws.Driver.check
    monkeypatch.setattr(dr_tws.Driver, "check",
                        lambda self, mode="program": real(self, "stale"))
    out = _run(cell_run)
    assert not out["correct"]
    assert out["checked"]["err_answers"]["value"] > \
        out["checked"]["err_answers"]["limit"]
