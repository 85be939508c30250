"""The correctness check of each cell at small sizes on the CPU: a sound
run passes its limits; the timed path broken underneath (the harness's
look for a chip skipped, the rest of a run driven as on the chip) fails
them; and the control, the reference in float8 put in the program's
place, fails them too."""
import pytest

from bench import control, run
from bench.tests import tiny
from bench.tests.conftest import ROOT

SERVE = ["danube-serve-longprompt", "danube-serve-longgen"]
# the limit at these small sizes, between the sound program's readings
# (at most 0.0023 over four seeds of each cell on the CPU) and the
# control's (at least 0.022)
TINY_LIMITS = {"served_logit_gap": 0.01}


def result(cell, seed=3):
    return run.run_cell(tiny.args(cell, seed=seed, seconds=1.0),
                        find_chips=tiny.cpu_chips,
                        adjust=tiny.shrink(limits=TINY_LIMITS))


def _serve_step(mp, change):
    from repro.launch import serve
    orig = serve._serve_step

    def step(cfg, base, bank, cache, tok, pos, rows):
        return change(orig, cfg, base, bank, cache, tok, pos, rows)
    mp.setattr(serve, "_serve_step", step)


def _state_unchanged_serve(mp):
    _serve_step(mp, lambda o, cfg, b, k, cache, t, p, r:
                (o(cfg, b, k, cache, t, p, r)[0], cache))


def _token_altered_serve(mp):
    def change(o, cfg, b, k, cache, t, p, r):
        nxt, new = o(cfg, b, k, cache, t, p, r)
        return nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), new
    _serve_step(mp, change)


def _half_batch_serve(mp):
    """Half of the slots left out of the step: they are handed the other
    half's tokens."""
    def change(o, cfg, b, k, cache, t, p, r):
        nxt, new = o(cfg, b, k, cache, t, p, r)
        h = nxt.shape[0] // 2
        return nxt.at[h:2 * h].set(nxt[:h]), new
    _serve_step(mp, change)


@pytest.mark.parametrize("cell", SERVE)
def test_a_sound_run_is_correct(cell):
    res = result(cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged_serve,
                                   _token_altered_serve, _half_batch_serve])
@pytest.mark.parametrize("cell", SERVE)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    res = result(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 77])
@pytest.mark.parametrize("cell", SERVE)
def test_the_control_is_not_correct(cell, seed):
    spec = run.read_json(ROOT / "BENCHMARK.json")
    workload = next(w for w in spec["workloads"] if w["name"] == cell)
    c = run.Cell(spec, workload, tiny.args(cell, seed=seed), run.CACHE / "t")
    tiny.shrink(limits=TINY_LIMITS)(c)
    driver = run.load_module(run.BENCH / "drivers" / f"{c.mix['driver']}.py",
                             f"bench_driver_{c.mix['driver']}")
    c.devices = tiny.cpu_chips(1, None)[0]
    got = control.readings(run, driver, c, driver.run(c), True)
    assert got["correct"] and not got["control_correct"], got
