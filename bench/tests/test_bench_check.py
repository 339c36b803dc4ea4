"""The output check, driven through a whole run at smoke size on the
CPU (the harness's look for a chip is skipped): sound runs of every
serving cell come out correct, a served token altered where the engine
produces it makes `correct` false, the lower-precision control put in
the program's place comes out not correct and reads far wider gaps than
the program, and a compile inside the window ends the run without a
result."""
import time

import pytest

from bench_smoke import CELLS, SERVE_CELLS, smoke, smoke_workload
import harness


def _run(cell, seed=2**35 + 11, control=None):
    cfg, _, _, _ = smoke(CELLS[cell])
    run = harness.driver("serve").run(
        config=cfg, workload=smoke_workload(cell), seed=seed, seconds=2.0,
        trace=False, t0=time.perf_counter(), peaks={}, control=control)
    spec = harness.benchmark_spec()
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run, harness.report(run, harness.cell_metrics(spec, cell, False),
                               dev, False)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_sound_run_is_correct(cell):
    _, res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    key = smoke_workload(cell)["check"]["compare"]
    assert set(res["checks"]) == {key, "served_positions"}
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e = harness.cell_metrics(harness.benchmark_spec(), cell, False)
    assert set(res["metrics"]) == {m["name"] for m in e2e}


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_altered_token_makes_the_run_incorrect(monkeypatch, cell):
    from repro.serve.engine import ContinuousEngine

    emit = ContinuousEngine._emit

    def altered(self, slot, req, tok, now):
        if len(req.tokens) == 2:      # the third token of every answer
            tok = (tok + 1) % self.cfg.vocab_size
        emit(self, slot, req, tok, now)

    monkeypatch.setattr(ContinuousEngine, "_emit", altered)
    _, res = _run(cell)
    assert not res["correct"]
    gap = res["checks"][smoke_workload(cell)["check"]["compare"]]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    run, res = _run(cell, seed=2**35 + 12, control="float8_e4m3fn")
    r = run.data["readings"]
    key = smoke_workload(cell)["check"]["compare"]
    gap = res["checks"][key]
    assert not res["correct"] and gap["value"] > gap["limit"]
    assert gap["value"] == r["control_" + key]
    assert r[key] <= gap["limit"]
    for k in ("gap_max", "gap_mean"):
        assert r["control_" + k] > 3 * max(r[k], 1e-4)


def test_compile_inside_the_window_ends_the_run(monkeypatch):
    """Blocks of 3 decode steps were never warmed; the rule changes as the
    window opens, on a backlog whose slots were filled before it, so the
    window's first step decodes a block of 3 whatever the clock reads."""
    serve = harness.driver("serve")
    window = serve.window

    def change_the_block_rule_as_it_opens(eng, tracker, items, seconds,
                                          drain_s, annotate, on_open,
                                          on_close, start=0.0):
        def opened():
            on_open()
            eng.decode_block = 3

        return window(eng, tracker, items, seconds, drain_s, annotate,
                      opened, on_close, start=start)

    monkeypatch.setattr(serve, "window", change_the_block_rule_as_it_opens)
    cell = next(c for c in SERVE_CELLS if smoke_workload(c)["traffic"]
                .get("fill_before_window"))
    cfg, _, _, _ = smoke(CELLS[cell])
    with pytest.raises(SystemExit, match="compiled inside the window"):
        serve.run(config=cfg, workload=smoke_workload(cell),
                  seed=2**35 + 13, seconds=2.0, trace=False,
                  t0=time.perf_counter(), peaks={})
