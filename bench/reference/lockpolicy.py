"""The policy arithmetic the reference simulator needs, copied from the
repository's ``repro.core.policy`` and ``repro.core.oracle`` so that the
reference imports nothing of the program: thread states, the mutable
lock's window rules (Algorithm 1), the four oracle families, the counter
RNG, and the workload, arrival and fault rows' scalar forms.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol

# -- thread states ----------------------------------------------------------
NCS, CS, SPIN, SLEEP_ST, WAKING, DONE = range(6)
STATE_NAMES = ("NCS", "CS", "SPIN", "SLEEP", "WAKING", "DONE")

#: Hardware-contention coefficient per discipline (the CS holder's rate is
#: divided by ``1 + alpha * n_spinners``): the models' defaults.
DEFAULT_ALPHA = {
    "tas": 0.05,
    "ttas": 0.02,
    "mcs": 0.0,
    "sleep": 0.0,
    "adaptive": 0.02,
    "mutable": 0.02,
    "fifo": 0.0,
    "fissile": 0.02,
    "hapax": 0.0,
    "ttas_backoff": 0.01,
}

BO_SALT = 0x165667B1
BO_CAP = 6

# -- oracle families ----------------------------------------------------------
EWMA_ONE = 256
EWMA_SHIFT = 3


def oracle_evalsws_row(spun, slept, sws, cnt, ewma, k):
    """Paper EvalSWS (E1-E12): double on a late wake-up, -1 after ``k``
    clean acquisitions."""
    cnt1 = cnt + 1
    late = slept * (1 - spun)
    hitk = (cnt1 >= k) * (1 - late)
    delta = late * sws + hitk * (-1)
    cnt1 = (1 - late) * (1 - hitk) * cnt1
    return delta, cnt1, ewma


def oracle_aimd_row(spun, slept, sws, cnt, ewma, k):
    """+1 on a late wake-up, halve after ``k`` clean rounds."""
    cnt1 = cnt + 1
    late = slept * (1 - spun)
    hitk = (cnt1 >= k) * (1 - late)
    delta = late * 1 + hitk * (-(sws // 2))
    cnt1 = (1 - late) * (1 - hitk) * cnt1
    return delta, cnt1, ewma


def oracle_fixed_row(spun, slept, sws, cnt, ewma, k):
    """The window pinned at the budget ``k``."""
    return k - sws, cnt * 0, ewma


def oracle_history_row(spun, slept, sws, cnt, ewma, k):
    """EWMA of the late-wake indicator in Q8 fixed point: double above
    twice the target rate ``1/(k+1)``, shrink by one below half of it."""
    late = slept * (1 - spun)
    ewma1 = ewma + ((late * EWMA_ONE - ewma) >> EWMA_SHIFT)
    target = EWMA_ONE // (k + 1)
    grow = (ewma1 > 2 * target) * 1
    shrink = (2 * ewma1 < target) * (1 - grow)
    delta = grow * sws + shrink * (-1)
    return delta, cnt * 0, ewma1


ORACLE_ROWS = {"paper": oracle_evalsws_row, "aimd": oracle_aimd_row,
               "fixed": oracle_fixed_row, "history": oracle_history_row}


class Oracle(Protocol):
    """Signed window variation computed at lock-acquire time."""

    def eval_sws(self, spun: bool, slept: bool, sws: int) -> int:
        ...


class RowOracle:
    """One oracle family with its ``(cnt, ewma)`` integer state."""

    def __init__(self, family: str = "paper", k: int = 10):
        if k < 1:
            raise ValueError("K must be >= 1")
        self.row = ORACLE_ROWS[family]
        self.k = k
        self.cnt = 0
        self.ewma = 0

    def eval_sws(self, spun: bool, slept: bool, sws: int) -> int:
        delta, self.cnt, self.ewma = self.row(
            int(spun), int(slept), sws, self.cnt, self.ewma, self.k)
        return int(delta)


def EvalSWS(k: int = 10) -> RowOracle:  # noqa: N802 (the paper's name)
    return RowOracle("paper", k)


# -- Algorithm 1: arrival and release decisions --------------------------------
def clamp_delta(sws: int, delta: int, lo: int, hi: int) -> int:
    """A16-A17: clamp so that ``lo <= sws + delta <= hi``."""
    if sws + delta < lo:
        delta = lo - sws
    if sws + delta > hi:
        delta = hi - sws
    return delta


def should_sleep_on_arrival(thc_pre: int, sws: int) -> bool:
    """A7: an arrival at index ``thc_pre`` sleeps iff it lands outside the
    spinning window."""
    return thc_pre >= sws


def wake_correction(delta: int, thc: int, sws_pre: int) -> int:
    """C1/C2 wake-up-count correction (A23-A33)."""
    sws_post = sws_pre + delta
    if delta < 0 and thc > sws_post:
        tmp = thc - sws_post
    elif delta > 0 and thc > sws_pre:
        tmp = thc - sws_pre
    else:
        tmp = 0
    sign = 1 if delta > 0 else -1
    return sign * min(abs(delta), tmp)


def latch_wuc(wuc: int) -> tuple[int, int]:
    """R2-R7: latch the wake-up count at release time."""
    if wuc >= 0:
        return wuc, 0
    return -1, wuc + 1


def release_quota(r_wuc: int, thc_pre: int, sws: int) -> int:
    """R11-R17: permits issued by this release."""
    if r_wuc < 0:
        return 0
    if thc_pre > sws:
        r_wuc += 1
    return r_wuc


# -- counter RNG and workload rows ----------------------------------------------
WL_CONSTANT, WL_BURSTY, WL_HETERO, WL_JITTER = range(4)
WORKLOAD_IDS = {"constant": WL_CONSTANT, "bursty": WL_BURSTY,
                "hetero": WL_HETERO, "jitter": WL_JITTER}
WL_PHASE_SALT = 0x7F4A7C15
WL_SPREAD_SALT = 0x6C62272E


def counter_uniform_scalar(seed: int, tid: int, ctr: int = 0) -> float:
    """Splitmix-style counter uniform in [0, 1) (mod 2**32 arithmetic)."""
    m = 0xFFFFFFFF
    x = (seed ^ (tid * 0x9E3779B9) ^ ((ctr + 1) * 0x85EBCA6B)) & m
    x ^= x >> 16
    x = (x * 0x7FEB352D) & m
    x ^= x >> 15
    x = (x * 0x846CA68B) & m
    x ^= x >> 16
    return x * 2.0 ** -32


def workload_thread_scale(spread_u, spread):
    """Per-thread multiplier, log-uniform in ``[1/spread, spread]``."""
    return spread ** (2.0 * spread_u - 1.0)


def workload_off_gate(now, phase_u, period, duty):
    """1.0 when a thread of phase ``phase_u`` is in the OFF part of its
    ON/OFF cycle at ``now``, else 0.0."""
    pos = (now / period + phase_u) % 1.0
    return (pos >= duty) * 1.0


# -- arrival rows ---------------------------------------------------------------------
AR_CLOSED, AR_POISSON, AR_BURSTY = range(3)
ARRIVAL_IDS = {"closed": AR_CLOSED, "poisson": AR_POISSON,
               "bursty": AR_BURSTY}
AR_SALT = 0x94D049BB
AR_PHASE_SALT = 0xBF58476D
QUEUE_MAX = 128

# -- fault rows ---------------------------------------------------------------------
FAULT_NONE, FAULT_PREEMPT, FAULT_OVERSUB, FAULT_LOSTWAKE, FAULT_JITTER = \
    range(5)
FAULT_IDS = {"none": FAULT_NONE, "preempt": FAULT_PREEMPT,
             "oversub": FAULT_OVERSUB, "lostwake": FAULT_LOSTWAKE,
             "jitter": FAULT_JITTER}
FLT_GATE_SALT = 0xA3C59AC3
FLT_WAKE_SALT = 0xC2B2AE35
FLT_MAG_SALT = 0x27220A95


@dataclass(frozen=True)
class FaultRow:
    name: str
    fid: int
    progress: object
    wake_delay: object


def _fault_progress_one(is_holder, gate_u, rate):
    return 1.0 + 0.0 * gate_u


def _fault_progress_preempt(is_holder, gate_u, rate):
    return 1.0 - (gate_u < rate) * 1.0


def _fault_progress_oversub(is_holder, gate_u, rate):
    return 1.0 - rate * gate_u


def _fault_wake_nominal(wake, w1, w2, rate, scale):
    return wake + 0.0 * w1


def _fault_wake_lost(wake, w1, w2, rate, scale):
    return wake + (w1 < rate) * (scale - wake)


def _fault_wake_jitter(wake, w1, w2, rate, scale):
    return wake + (w1 < rate) * scale * w2


FAULT_ROWS = {
    "none": FaultRow("none", FAULT_NONE,
                     _fault_progress_one, _fault_wake_nominal),
    "preempt": FaultRow("preempt", FAULT_PREEMPT,
                        _fault_progress_preempt, _fault_wake_nominal),
    "oversub": FaultRow("oversub", FAULT_OVERSUB,
                        _fault_progress_oversub, _fault_wake_nominal),
    "lostwake": FaultRow("lostwake", FAULT_LOSTWAKE,
                         _fault_progress_one, _fault_wake_lost),
    "jitter": FaultRow("jitter", FAULT_JITTER,
                       _fault_progress_one, _fault_wake_jitter),
}
