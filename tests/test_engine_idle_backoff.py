"""The cycle thread's idle back-off (``ops/engine.py`` ``_background_loop``).

A cycle that did something is followed by a wait of ``cycle_time_s``; each
cycle that did nothing doubles the wait, up to ``IDLE_WAIT_CAP_S`` (and to
a quarter of the controller's round deadline).  No test here reads a
clock: ``RecordingWake`` stands in for the engine's ``_wake`` event,
returns at once and keeps the timeout each wait asked for.
"""

import threading

import numpy as np
import pytest

from horovod_tpu.ops.engine import IDLE_WAIT_CAP_S, CollectiveType
from horovod_tpu.ops.scheduler import CheckpointChunk

CYCLE_S = 0.001


class RecordingWake:
    """``engine._wake`` for a loop driven on the test's own thread: each
    ``wait`` records ``(timeout, was set)``, runs the hook the test gave
    that turn — an enqueue lands while the loop sleeps — and returns at
    once; the last turn stops the loop (it still runs that turn's cycle)."""

    def __init__(self, eng, turns, hooks=None):
        self.eng, self.turns, self.hooks = eng, turns, hooks or {}
        self.timeouts, self.was_set, self._flag = [], [], False

    def wait(self, timeout=None):
        turn = len(self.timeouts)
        hook = self.hooks.get(turn)
        if hook is not None:
            hook()
        self.timeouts.append(timeout)
        self.was_set.append(self._flag)
        if turn + 1 >= self.turns:
            self.eng._shutdown.set()
        return self._flag

    def set(self):
        self._flag = True

    def clear(self):
        self._flag = False

    def is_set(self):
        return self._flag


class FakeController:
    """Lock-step rounds in which every announced entry is ready at once,
    but for the first ``hold`` rounds; quiet by default, as every fake
    controller of the suite is (it has none of the three attributes).
    ``on_round`` runs inside each round, where the cycle thread blocks."""

    def __init__(self, hold=0, on_round=None, **attrs):
        self.rounds, self.hold, self.on_round = 0, hold, on_round
        self.seen = []
        for k, v in attrs.items():
            setattr(self, k, v)

    def negotiate(self, entries):
        self.rounds += 1
        self.seen.append([e.name for e in entries])
        if self.on_round is not None:
            self.on_round(self)
        if self.rounds <= self.hold:
            return [], []
        return list(entries), []

    def slot_of(self, e):
        return -1

    def forget(self, e):
        pass


@pytest.fixture()
def eng(hvd):
    """An engine of its own, never started: the tests run its loop."""
    from horovod_tpu.common import basics
    from horovod_tpu.ops.engine import CollectiveEngine
    eng = CollectiveEngine(basics._get_state())
    eng.cycle_time_s = eng.idle_wait_s = CYCLE_S
    return eng


def run_loop(eng, turns, hooks=None):
    wake = eng._wake = RecordingWake(eng, turns, hooks)
    eng._background_loop()
    return wake


def submit(eng, hvd, name):
    world = hvd.size()
    x = hvd.stack_per_rank([np.full((4,), r, np.float32)
                            for r in range(world)])
    from horovod_tpu.ops import collectives as C
    return eng.enqueue(name, CollectiveType.ALLREDUCE, x,
                       reduce_op=C.ReduceOp.SUM)


def doubling(first_idle_turn, turns, cap=IDLE_WAIT_CAP_S):
    """The waits of a loop whose cycles do something up to turn
    ``first_idle_turn`` and nothing from there on."""
    out = [CYCLE_S] * (first_idle_turn + 1)
    while len(out) < turns:
        out.append(max(CYCLE_S, min(out[-1] * 2, cap)))
    return out[:turns]


def test_waits_double_to_the_ceiling_and_fall_back_after_an_entry(eng, hvd):
    eng.controller = FakeController()
    handles = []
    wake = run_loop(eng, 11, {8: lambda: handles.append(
        submit(eng, hvd, "late"))})
    ms = [round(t * 1e3, 6) for t in wake.timeouts]
    assert ms == [1, 2, 4, 8, 16, 32, 32, 32, 32, 1, 2], ms
    assert IDLE_WAIT_CAP_S == 0.032
    # the enqueue set the event before the wait it interrupted returned
    assert wake.was_set[8] and not any(wake.was_set[:8])
    total = np.asarray(eng.synchronize(handles[0], timeout=60))
    np.testing.assert_array_equal(
        total, np.full((4,), sum(range(hvd.size())), np.float32))
    # cycles 0-7 and 9-10 did nothing; 8 carried the entry
    assert eng.idle_cycles == 10
    assert eng.cycle_count == 11
    assert eng.idle_wait_s == 0.004


def _held_entry(eng, hvd):
    eng.controller = FakeController(hold=3)
    submit(eng, hvd, "not_ready")
    return 3        # ready, and so still doing something, in cycle 3


def _backlog(eng, hvd):
    eng.controller = FakeController()
    eng.ckpt_lane_budget = 1
    eng.submit_checkpoint_io(
        [CheckpointChunk(f"c{i}", run=lambda: None) for i in range(4)])
    return 2        # one chunk a cycle: the backlog empties in cycle 3


def _staged_checkpoint_item(eng, hvd):
    def stage(ctl):
        # the training thread commits while the cycle thread is in a round
        if ctl.rounds <= 3:
            eng.submit_checkpoint_io(
                [CheckpointChunk(f"s{ctl.rounds}", run=lambda: None)])

    eng.controller = FakeController(on_round=stage)
    return 2        # staged in cycles 0-2; cycle 3 runs the last, stages none


def _flag_for_three_rounds(name, busy, calm):
    def arrange(eng, hvd):
        def flip(ctl):
            setattr(ctl, name, busy if ctl.rounds <= 3 else calm)

        eng.controller = FakeController(on_round=flip)
        return 2
    return arrange


@pytest.mark.parametrize("arrange", [
    pytest.param(lambda eng, hvd: setattr(
        eng, "controller", FakeController()) or -1, id="control-nothing"),
    pytest.param(_held_entry, id="not-ready-requeue"),
    pytest.param(_backlog, id="backlog"),
    pytest.param(_staged_checkpoint_item, id="staged-checkpoint-item"),
    pytest.param(_flag_for_three_rounds("inflight_rounds", 1, 0),
                 id="outstanding-pipelined-round"),
    pytest.param(_flag_for_three_rounds("join_open", True, False),
                 id="join-pending-or-open"),
    pytest.param(_flag_for_three_rounds("last_round_quiet", False, True),
                 id="another-ranks-verdict"),
])
def test_a_cycle_that_did_something_keeps_the_short_wait(eng, hvd, arrange):
    """Each case holds its condition through cycle ``last_busy``; the wait
    stays ``cycle_time_s`` until the cycle after it and doubles from
    there.  The control case has no condition and doubles at once."""
    last_busy = arrange(eng, hvd)
    wake = run_loop(eng, 8)
    assert wake.timeouts == pytest.approx(doubling(last_busy + 1, 8))
    assert eng.idle_cycles == 8 - (last_busy + 1)


def test_a_quarter_of_the_round_deadline_caps_the_wait(eng):
    eng.controller = FakeController(round_timeout_s=0.04)
    wake = run_loop(eng, 7)
    assert wake.timeouts == pytest.approx(
        [0.001, 0.002, 0.004, 0.008, 0.01, 0.01, 0.01])


def test_the_wait_is_never_under_the_cycle_time(eng):
    """``HOROVOD_CYCLE_TIME`` above the ceiling: the wait is what it was."""
    eng.controller = FakeController(round_timeout_s=0.04)
    eng.cycle_time_s = eng.idle_wait_s = 0.05
    wake = run_loop(eng, 4)
    assert wake.timeouts == pytest.approx([0.05] * 4)
    assert eng.idle_cycles == 4


def test_the_autotuners_cycle_time_is_the_wait_after_work(eng, hvd):
    """The autotuner walks ``cycle_time_s`` while the loop runs: the wait
    after a cycle with an entry is the value then in force."""
    eng.controller = FakeController()

    def retune():
        eng.cycle_time_s = 0.003
        submit(eng, hvd, "tuned")

    wake = run_loop(eng, 5, {2: retune})
    assert wake.timeouts == pytest.approx(
        [0.001, 0.002, 0.004, 0.003, 0.006])


def test_an_entry_enqueued_inside_a_round_is_drained_by_the_next_cycle(
        eng, hvd):
    """The cycle thread is blocked in an (empty) round after three idle
    cycles when the entry arrives.  The enqueue's ``set`` lands after the
    loop's ``clear``, so the wait that follows the round returns at once,
    whatever timeout it asks for, and the very next cycle drains the
    entry: no idle wait lies between."""
    handles = []

    def enqueue_in_round_four(ctl):
        if ctl.rounds == 4:
            handles.append(submit(eng, hvd, "mid_round"))

    ctl = eng.controller = FakeController(on_round=enqueue_in_round_four)
    wake = run_loop(eng, 6)
    assert ctl.seen[:6] == [[], [], [], [], ["mid_round"], []]
    # the wait before cycle 4 found the event set: it did not sleep
    assert wake.was_set == [False, False, False, False, True, False]
    assert wake.timeouts[5] == CYCLE_S      # after the cycle with the entry
    eng.synchronize(handles[0], timeout=60)


def test_single_controller_mode_backs_off_and_kick_dispatches_inline(
        eng, hvd):
    assert eng.controller is None
    wake = run_loop(eng, 4)
    assert wake.timeouts == pytest.approx([0.001, 0.002, 0.004, 0.008])
    assert eng.idle_cycles == 4 and eng.cycle_count == 0
    # kick() runs the cycle on the calling thread: the loop is not running
    eng._wake = threading.Event()
    h = submit(eng, hvd, "inline")
    eng.kick()
    assert eng.idle_wait_s == CYCLE_S and eng.cycle_count == 1
    assert eng.pipeline_dispatches == 1     # dispatched before kick returned
    eng.synchronize(h, timeout=60)


def test_every_waker_sets_the_event(eng, hvd):
    """``submit``, ``kick`` with a controller, the checkpoint lane,
    leftovers in the backlog, ``quiesce`` and ``stop`` each end a wait."""
    eng.controller = FakeController()
    wake = eng._wake = RecordingWake(eng, 0)

    def sets(fn):
        wake.clear()
        fn()
        return wake.is_set()

    assert sets(lambda: submit(eng, hvd, "w"))
    assert sets(eng.kick)
    assert sets(lambda: eng.submit_checkpoint_io(
        [CheckpointChunk("k0", run=lambda: None),
         CheckpointChunk("k1", run=lambda: None)]))
    eng.ckpt_lane_budget = 1
    assert sets(eng.run_loop_once)          # one chunk ran, one is left
    assert sets(eng.quiesce)
    assert sets(eng.stop)


def test_join_sets_the_event_and_reads_as_open(hvd, monkeypatch):
    from horovod_tpu.ops import eager

    class Joining(FakeController):
        join_open = False

        def request_join(self):
            self.join_open = True

        def join_wait(self, timeout):
            return 7

    engine = eager._engine()
    wake = RecordingWake(engine, 0)
    monkeypatch.setattr(engine, "_wake", wake)
    monkeypatch.setattr(engine, "controller", Joining())
    assert eager.join(timeout=1) == 7
    assert wake.is_set() and engine.controller.join_open


# ------------------------------------------------- the controller's side
def test_join_open_on_the_controller():
    from horovod_tpu.common.controller import TCPController
    ctl = TCPController.__new__(TCPController)
    ctl._joined = ctl._join_pending = False
    ctl._join_event = threading.Event()
    assert not ctl.join_open
    ctl.request_join()
    assert ctl.join_open                    # pending
    ctl._join_pending, ctl._joined = False, True
    assert ctl.join_open                    # joined, peers still running
    ctl._joined = False
    assert not ctl.join_open


def test_a_round_is_quiet_only_if_it_carried_no_verdict_for_anybody():
    """Two real clients and the native server.  Rank 1 announces nothing
    throughout: an empty round is quiet on both; a round in which rank 0
    alone announces a one-rank collective (``required`` 1, as a process
    set of one) carries its slot assignment and its ready verdict to
    rank 1 too, which is what keeps a non-member on the short wait."""
    from horovod_tpu.common.controller import TCPController
    from horovod_tpu.common.net import free_ports
    (port,) = free_ports(1)
    quiet = {0: [], 1: []}
    errors = []
    step = threading.Barrier(2, timeout=30)

    def worker(rank):
        ctl = None
        try:
            ctl = TCPController("127.0.0.1", port, rank=rank, world=2)
            assert ctl.last_round_quiet             # before any round
            mine = ("\x1fset1\x1fonly0", 1, "f32:4", "-1", "-1", "")
            for announces in ([], [mine] if rank == 0 else [], [], []):
                _ready, _warns, errs = ctl._round(announces)
                assert not errs
                quiet[rank].append(ctl.last_round_quiet)
            step.wait()
        except Exception as exc:  # noqa: BLE001 - surfaced by the assert
            errors.append(exc)
            step.abort()
        finally:
            if ctl is not None:
                ctl.shutdown()

    t = threading.Thread(target=worker, args=(1,), daemon=True)
    t.start()
    worker(0)
    t.join(timeout=30)
    assert not errors, errors
    assert quiet[0] == quiet[1], quiet
    assert quiet[0][0] is True and quiet[0][1] is False, quiet
    assert quiet[0][-1] is True, quiet
