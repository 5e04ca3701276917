"""C1/C2 wake-up-count correction paths of MutableLock (Algorithm 1
A23-A33, R2-R7) — the grow-with-sleepers and shrink-with-excess-spinners
cases, exercised deterministically (scripted oracle + phantom waiters on
the packed lstate word) and with real threads.

Phantom-waiter technique: ``lstate.fetch_add(k)`` registers k waiters
exactly as k concurrent ``acquire()`` calls would (A4) without parking real
threads, so the correction arithmetic observed by the next acquirer is
deterministic.  Wake permits issued toward phantoms land in the semaphore
(banked), where we can count them.
"""

import threading
import time

import pytest

from repro.core.mutlock import MutableLock
from repro.core.oracle import EvalSWS


class ScriptedOracle:
    """Replays a fixed delta sequence (then zeros)."""

    def __init__(self, deltas):
        self.deltas = list(deltas)
        self.calls = []

    def eval_sws(self, spun, slept, sws):
        self.calls.append((spun, slept, sws))
        return self.deltas.pop(0) if self.deltas else 0


# --------------------------------------------------------------------------
# Deterministic single-thread drives of the correction arithmetic
# --------------------------------------------------------------------------
def test_c1_grow_with_sleepers_banks_wakeups():
    """Grow by +2 while 3 phantom threads wait outside the window: C1 must
    schedule exactly 2 extra wake-ups (A27-A33), shipped at release on top
    of the R16-17 sleep->spin promotion."""
    lock = MutableLock(max_sws=8, initial_sws=1,
                       oracle=ScriptedOracle([+2]))
    lock.lstate.fetch_add(3)            # 3 phantom waiters (A4 x3)
    lock.slp_obj.wake_up(1)             # pre-bank a permit so A9 won't park
    lock.acquire()                      # thc 3 -> 4; slept=True, spun=False
    assert lock.sws == 3                # 1 + 2
    assert lock.thc == 4
    # A27-A28: thc(4) > sws_pre(1) -> tmp = 3; wuc += min(2, 3) = 2
    assert lock.wuc == 2

    sem_before = lock.slp_obj.wakes
    lock.release()
    # R3: r_wuc = 2; R16: thc_pre(4) > sws(3) -> +1 => 3 permits issued
    assert lock.wuc == 0
    assert lock.slp_obj.wakes - sem_before == 3


def test_c2_shrink_with_excess_spinners_suppresses_wakeups():
    """Shrink by -2 while 3 phantom spinners sit inside the window: C2 must
    bank 2 wake-up suppressions (A25-A26), and the next two releases must
    issue no wake-up at all (R6-R7, R11-R12)."""
    lock = MutableLock(max_sws=8, initial_sws=4,
                       oracle=ScriptedOracle([-2]))
    lock.lstate.fetch_add(3)            # 3 phantoms inside the window
    lock.acquire()                      # thc 3 -> 4 < sws=4: no sleep
    assert lock.sws == 2
    # A25-A26: thc(4) > sws_post(2) -> tmp = 2; wuc -= min(2, 2)
    assert lock.wuc == -2

    w0 = lock.slp_obj.wakes
    lock.release()                      # R7: wuc -2 -> -1; no wake-up
    assert lock.wuc == -1
    assert lock.slp_obj.wakes == w0     # suppressed

    # the next acquire lands outside the shrunken window (thc 3 >= sws 2):
    # pre-bank a permit so the phantom-backed sleep doesn't park for real
    lock.slp_obj.wake_up(1)
    lock.acquire()
    w1 = lock.slp_obj.wakes
    lock.release()                      # R7 again: wuc -1 -> 0; no wake-up
    assert lock.wuc == 0
    assert lock.slp_obj.wakes == w1     # second suppression

    # debt paid: the next release ships wake-ups again (R16 promotion)
    lock.slp_obj.wake_up(1)
    lock.acquire()
    w2 = lock.slp_obj.wakes
    lock.release()                      # r_wuc=0; thc_pre(4) > sws(2) -> +1
    assert lock.slp_obj.wakes == w2 + 1


def test_c2_clamp_never_drops_window_below_one():
    lock = MutableLock(max_sws=4, initial_sws=1,
                       oracle=ScriptedOracle([-3, -3]))
    lock.acquire()
    assert lock.sws == 1                # A16 clamp: delta -> 0
    assert lock.wuc == 0
    lock.release()


# --------------------------------------------------------------------------
# Real multi-thread drives
# --------------------------------------------------------------------------
def _run_workers(lock, n_threads, iters, cs=2e-5):
    counter = [0]
    # all threads contend from the first acquire: started one by one on a
    # loaded host, the first could finish its early iterations alone
    start = threading.Barrier(n_threads)

    def worker():
        start.wait()
        for _ in range(iters):
            with lock:
                counter[0] += 1
                time.sleep(cs)          # releases the GIL
    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return counter[0]


@pytest.mark.parametrize("deltas", [[+2] * 4, [-1] * 6, [+3, -2] * 3])
def test_multithread_corrections_preserve_liveness(deltas):
    """Resizes with C1/C2 corrections while real threads sleep and spin:
    no thread may be lost (every wake-up owed is eventually delivered) and
    mutual exclusion must hold."""
    lock = MutableLock(max_sws=4, initial_sws=1,
                       oracle=ScriptedOracle(list(deltas)),
                       record_stats=True)
    done = _run_workers(lock, n_threads=6, iters=8)
    assert done == 48                   # no lost updates, no deadlock
    assert lock.thc == 0                # everyone checked out (A4/R9 paired)
    assert 1 <= lock.sws <= 4
    assert lock.stats.acquisitions == 48


def test_multithread_grow_with_sleepers_delivers_extra_wakeups():
    """With a window pinned small and then grown under load, the C1 path
    must deliver more wake-ups than sleeps would otherwise get: the grown
    window admits sleepers without waiting for one-release-one-wake."""
    lock = MutableLock(max_sws=6, initial_sws=1,
                       oracle=ScriptedOracle([0, 0, +4]),
                       record_stats=True)
    done = _run_workers(lock, n_threads=6, iters=10)
    assert done == 60
    assert lock.thc == 0
    assert lock.sws >= 5                # the scripted grow landed
    assert lock.slp_obj.sleeps > 0      # contention did park threads
    # every parked thread was eventually woken (conservation)
    assert lock.slp_obj.wakes >= lock.slp_obj.sleeps \
        - lock.slp_obj._sem._value


def test_multithread_adaptive_oracle_end_to_end():
    """The real EvalSWS under contention: acquisitions equal the work done
    and the window stays in bounds (sanity net for the paths above)."""
    lock = MutableLock(max_sws=4, oracle=EvalSWS(k=5), record_stats=True)
    done = _run_workers(lock, n_threads=5, iters=10)
    assert done == 50
    assert 1 <= lock.sws <= 4
    assert lock.stats.late_wakeups >= 0
