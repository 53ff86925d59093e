"""The ``loop`` mode's ``ahead``: losses read that many steps late, so that
the device has steps queued while the host stands still. Held here with a
trainer that only records the order of calls: what is sent before what is
read, that every loss sent is read before the clock is, and that ``ahead`` 0
is the loop as it was."""
import os
import time

import pytest

from chipbench import manifest
from chipbench.runners import train

HERE = os.path.dirname(os.path.abspath(__file__))


class Loss:
    def __init__(self, events, n, read_s):
        self.events, self.n, self.read_s = events, n, read_s

    def asscalar(self):
        time.sleep(self.read_s)
        self.events.append(("read", self.n))
        return 10.0 - self.n


class Recorder:
    """Stands for the trainer: ``step()`` hands back a loss that says when
    it is read."""

    def __init__(self, read_s=0.0):
        self.events, self.read_s = [], read_s

    def step(self, x, y):
        n = sum(1 for kind, _ in self.events if kind == "step")
        self.events.append(("step", n))
        return Loss(self.events, n, self.read_s)


def drive(ahead, sync_every=1, read_s=0.0, **length):
    trainer, reads, step_ms = Recorder(read_s), [], []
    ring = [(i, i) for i in range(4)]
    taken, elapsed = train.loop_window(trainer, ring, 0, sync_every, ahead,
                                       train.Spans(), reads, step_ms,
                                       **length)
    return trainer.events, reads, step_ms, taken, elapsed


@pytest.mark.parametrize("ahead", [0, 1, 4])
def test_a_loss_is_read_once_that_many_later_steps_are_sent(ahead):
    events, reads, step_ms, taken, _ = drive(ahead, steps=12)
    assert taken == len(step_ms) == 12
    for n in range(12):
        sent_before = [m for kind, m in events[:events.index(("read", n))]
                       if kind == "step"]
        assert max(sent_before) == min(n + ahead, 11)
    # every loss is read, in the order sent, under its own batch's index
    assert [at for at, _ in reads] == [n % 4 for n in range(12)]
    assert [loss for _, loss in reads] == [10.0 - n for n in range(12)]


def test_ahead_nought_is_the_loop_as_it_was():
    events = drive(0, steps=5)[0]
    assert events == [(kind, n) for n in range(5)
                      for kind in ("step", "read")]


def test_the_clock_is_read_after_every_loss_due():
    t0 = time.perf_counter()
    events, reads, _, taken, elapsed = drive(3, read_s=0.02, seconds=0.1)
    wall = time.perf_counter() - t0
    assert len(reads) == taken and events[-1] == ("read", taken - 1)
    # the three losses left when the time was up are waited for, and count
    assert 0.1 + 3 * 0.02 <= elapsed <= wall


def test_a_loss_not_due_is_still_waited_for_at_the_close():
    events, reads, _, taken, _ = drive(1, sync_every=2, steps=5)
    assert taken == 5
    assert [n for kind, n in events if kind == "read"] == [1, 3, 4]
    assert events[-1] == ("read", 4)


def traffic_files():
    folder = os.path.join(manifest.ROOT, "chipbench", "traffic")
    return sorted(f for f in os.listdir(folder) if f.endswith(".json"))


@pytest.mark.parametrize("name", traffic_files())
def test_a_traced_loop_is_mostly_steady_state(name):
    """The first ``ahead`` turns of a window only fill the queue: the traced
    window's median turn (``step_ms_p50``) is a steady one where they are
    under a fifth of it."""
    traffic = manifest.load_traffic(name[:-len(".json")])
    if traffic.get("mode") != "loop":
        return
    assert 0 <= traffic.get("ahead", 0) * 5 <= traffic["trace_steps"]
