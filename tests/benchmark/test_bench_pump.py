"""The arrival pump's bookkeeping, on a stand-in scheduler whose rounds
take a fixed time: which tokens the window counts, which slot served
each request, and the check's sample of one request per slot."""
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the repo and src/ on the path)
from bench import harness, traffic


class RoundEngine:
    """The scheduler's public pump, each round sleeping ``round_s`` and
    giving every request in a slot one token."""

    def __init__(self, slots: int, round_s: float):
        self.slots, self.round_s = slots, round_s
        self._slot = [None] * slots
        self.tok = jnp.zeros(())
        self.allocator = types.SimpleNamespace(pages_in_use=0)
        for k in harness.COUNTERS:
            setattr(self, k, 0)
        self.arrivals = []           # (uid, time on the pump's clock)

    def free_slots(self):
        return [i for i, r in enumerate(self._slot) if r is None]

    def is_busy(self):
        return any(r is not None for r in self._slot)

    def try_admit(self, req, now=0.0):
        free = self.free_slots()
        if not free:
            return False
        self._slot[free[0]] = req
        return True

    def step_round(self, elapsed):
        time.sleep(self.round_s)
        t = elapsed()
        for s, r in enumerate(self._slot):
            if r is None:
                continue
            r.out_tokens.append(0)
            self.arrivals.append((r.uid, t))
            if len(r.out_tokens) >= r.max_new:
                r.done, self._slot[s] = True, None
        self.chunks_run += 1
        self.host_transfers += 1


def _req(uid, due, max_new, prompt=4):
    return traffic.Req(uid, due, np.zeros(prompt, np.int32), max_new)


def test_window_counts_the_tokens_that_reached_the_host_in_it():
    """A pre-roll whose third round crosses the opening: request 0
    finishes in that round and request 1 goes on.  Only tokens that
    reached the host at 0 <= t <= seconds count, those of the crossing
    round included."""
    eng = RoundEngine(slots=2, round_s=0.2)
    seconds = 0.6
    pump = harness.Pump(eng, [_req(0, -0.5, 3), _req(1, -0.5, 8)],
                        seconds, drain=False, spans=False, preroll=0.5)
    pump.run()
    arrived = {0: [], 1: []}
    for uid, t in eng.arrivals:
        arrived[uid].append(t)
    # the scenario happened: request 0's last token came in the first
    # round that ended inside the window, after two that ended before it
    assert arrived[0][1] < 0.0 <= arrived[0][2] <= seconds
    want = {uid: sum(1 for t in ts if 0.0 <= t <= seconds)
            for uid, ts in arrived.items()}
    got = {log.uid: log.tokens_in_window for log in pump.logs}
    assert got == want
    assert pump.logs[0].tokens_at_open == 2


def test_slots_recorded_and_sample_covers_each_slot():
    eng = RoundEngine(slots=3, round_s=0.01)
    reqs = [_req(i, 0.01 * i, 2 + (i % 4), prompt=4 + i) for i in range(12)]
    pump = harness.Pump(eng, reqs, 1.0, drain=True, spans=False)
    pump.run()
    slots = {log.slot for log in pump.logs}
    assert slots == {0, 1, 2}
    seqs = harness.sample_requests(pump, seed=2 ** 40 + 3, most=3)
    assert len(seqs) == 3
    picked = {tuple(p) + (len(s),) for p, s in seqs}
    by_key = {tuple(r.prompt.tolist()) + (len(r.out_tokens),): log.slot
              for r, log in zip(pump.reqs, pump.logs)}
    assert {by_key[k] for k in picked} == {0, 1, 2}
    longest = max(pump.reqs, key=lambda r: len(r.prompt)
                  + len(r.out_tokens))
    assert (tuple(longest.prompt.tolist()) + (len(longest.out_tokens),)
            in picked)


@pytest.mark.parametrize("most", [1, 2])
def test_sample_keeps_the_longest_when_capped(most):
    eng = RoundEngine(slots=3, round_s=0.01)
    reqs = [_req(i, 0.01 * i, 3, prompt=4 + i) for i in range(6)]
    pump = harness.Pump(eng, reqs, 1.0, drain=True, spans=False)
    pump.run()
    seqs = harness.sample_requests(pump, seed=7, most=most)
    assert len(seqs) == most
    assert len(seqs[0][0]) == 9          # the longest prompt, 4 + 5
