"""The comparison that decides ``correct``, at a size the CPU holds: sound
runs of every cell pass it, and the bf16 control (the program fed values
one precision below the configuration's float32) fails it."""
import sys
import time
import weakref
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.tests import tinytree  # noqa: E402

CELLS = ["stencil2d_1024.replay", "rmat_s14.oneshot",
         "stencil2d_1024.oneshot", "rmat_s14.replay"]
SEED = 2**31 + 977  # seeds may need more than 32 signed bits


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinytree.make(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, name):
    lines, res = harness.run_cell(harness.load_cell(tiny, name), SEED, 0.2)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert lines[1]["window"]["compiles"] == 0
    reports = harness.load_cell(tiny, name).traffic["reports"]
    assert set(res["metrics"]) == {reports, "setup_s"}  # no HBM off the chip


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_is_not_correct(tiny, name):
    _, res = harness.run_cell(harness.load_cell(tiny, name), SEED, 0.2,
                              control="bf16")
    assert not res["correct"]
    cmp = res["compared"]
    assert cmp["value_err"]["value"] > cmp["value_err"]["limit"]


def test_same_seed_same_inputs():
    a = [harness.np.asarray(v) for v in harness.value_pool(SEED, 64, 2)]
    b = [harness.np.asarray(v) for v in harness.value_pool(SEED, 64, 2)]
    c = [harness.np.asarray(v) for v in harness.value_pool(SEED + 1, 64, 2)]
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all() and not (a[0] == a[1]).all()


class Output:
    pass


class CountingDriver:
    """Counts, at each call, the earlier outputs still alive."""

    def __init__(self):
        self.refs, self.alive = [], []

    def call(self, i):
        time.sleep(0.002)
        self.alive.append(sum(r() is not None for r in self.refs))
        out = Output()
        self.refs.append(weakref.ref(out))
        return i, out


@pytest.mark.parametrize("seed", [6000000031, 6000000034, 7, SEED])
def test_window_keeps_at_most_keep_outputs_alive(seed):
    """Whichever calls the seed's reservoir keeps, no call runs with more
    than KEEP earlier outputs alive, so the device's peak does not depend
    on the seed."""
    drv = CountingDriver()
    win = harness.run_window(drv, 0, 0.05, seed)
    assert win["attempted"] >= 4
    assert max(drv.alive) == harness.KEEP


class QueuedOutput:
    """An output that is ready once waited for; counts its waits."""

    def __init__(self, waits):
        self.waits = waits

    def block_until_ready(self):
        time.sleep(0.001)
        self.waits.append(id(self))
        return self


class QueuingDriver(CountingDriver):
    """Sends without waiting, as the replay driver does."""

    def __init__(self):
        super().__init__()
        self.waits = []

    def call(self, i):
        self.alive.append(sum(r() is not None for r in self.refs))
        out = QueuedOutput(self.waits)
        self.refs.append(weakref.ref(out))
        return i, out


@pytest.mark.parametrize("ahead", [1, 4])
@pytest.mark.parametrize("seed", [6000000034, SEED])
def test_window_ahead_keeps_keep_plus_ahead_outputs_alive(seed, ahead):
    """With calls queued ahead, no call is sent with more than KEEP + ahead
    earlier outputs alive, and the queued ones are there in every run."""
    drv = QueuingDriver()
    win = harness.run_window(drv, 0, 0.05, seed, ahead=ahead)
    assert win["attempted"] >= 2 * (ahead + harness.KEEP)
    assert max(drv.alive) == harness.KEEP + ahead


@pytest.mark.parametrize("ahead", [0, 3])
def test_window_waits_for_every_call_it_sent(ahead):
    """When the time is up nothing more is sent and every call sent is
    waited for before the clock is read: all the work over all the time."""
    drv = QueuingDriver()
    t0 = time.perf_counter()
    win = harness.run_window(drv, 0, 0.03, SEED, ahead=ahead)
    assert len(drv.waits) == win["attempted"] == len(win["call_ms"])
    assert win["elapsed_s"] >= sum(win["call_ms"]) * 1e-3 - 1e-6
    assert win["elapsed_s"] <= time.perf_counter() - t0
    assert win["failed"] == 0 and win["gc_ms"] >= 0


def test_ahead_is_fixed_by_the_files():
    """Calls queued ahead follow from the traffic's ahead_products and the
    configuration's f_m alone: about 3.5 s of replays on a v5e in both
    configurations, none for the one-shot (whose host driver waits)."""
    bench = Path(harness.BENCH_DIR)
    got = {name: harness.ahead_calls(harness.load_cell(bench, name))
           for name in CELLS}
    assert got == {"stencil2d_1024.replay": 4, "rmat_s14.replay": 2,
                   "stencil2d_1024.oneshot": 0, "rmat_s14.oneshot": 0}
