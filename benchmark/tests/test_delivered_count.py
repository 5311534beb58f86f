"""``EngineProbe.delivered``: the tokens that reached the host, each
request capped at what it asked.  A scripted stand-in engine (no model,
no device) is driven round by round; the count between two rounds must be
exactly the tokens whose arrival lies between them, wherever the requests
that carry them begin and end."""
import types

import numpy as np
import pytest

from harness import loader

STEPS = 4          # tokens a slot gets from one fused decode window


class ScriptedEngine:
    """Two slots; ``prefill`` hands each admitted row its first token and
    ``decode_window`` hands every active slot ``STEPS`` more, past the
    request's end too, as the program's fused window does."""

    decode_steps = STEPS

    def prefill(self, prompts, p_lens, admit):
        return np.zeros(len(admit), np.int32)

    def decode_window(self, active):
        return types.SimpleNamespace(
            counts=np.where(np.asarray(active), STEPS, 0))


def _probe():
    closed_loop = loader.load_module("runners", "closed_loop")
    engine = ScriptedEngine()
    return closed_loop.EngineProbe(engine), engine


def _admit(probe, engine, slot, rid, asked):
    """Request ``rid`` takes ``slot``: its prompt is its slot's number
    and its own, so that the probe can tell the requests apart."""
    prompt = np.array([slot, int(rid[1:])], np.int32)
    probe.expect(rid, prompt, asked)
    prompts = np.zeros((2, 2), np.int32)
    prompts[slot] = prompt
    engine.prefill(prompts, np.array([2, 2]), np.arange(2) == slot)


# Slot 0 serves A, then C; slot 1 serves B.  One decode window a round.
#
#   before the window   A admitted (1 token), one decode round (+4): A has 5
#   ---- the window opens ----
#   round 1             decode: A +4 -> 9, asked 7: capped, 2 of the 4 count
#                       B admitted: 1
#   round 2             C admitted on A's slot: 1; decode: B +4, C +4
#   round 3             decode: B +4 -> 9; C +4 -> 9, asked 6: 1 of the 4
#   ---- the window closes ----
#   round 4             decode: B +4 -> 13
#
# A begins before the window and ends inside it; B begins inside and ends
# inside (asked 9) or after (asked 13); C's last window delivers 3 steps
# more than it asked.  Delivered inside the window, whatever B asked:
# A 2, B 1 + 4 + 4 = 9, C 1 + 4 + 1 = 6: 17.  The old count, the tokens of
# the requests that completed inside, is A 7 + C 6 = 13 with B ending
# after the window and 22 with B ending in its last round.
@pytest.mark.parametrize("b_asked, completed_inside", [(9, 22), (13, 13)])
def test_count_is_the_arrivals_inside_the_window(b_asked, completed_inside):
    probe, engine = _probe()
    asked = {"r0": 7, "r1": b_asked, "r2": 6}
    _admit(probe, engine, 0, "r0", asked["r0"])
    engine.decode_window(np.array([True, False]))
    assert probe.delivered() == 5

    at_open = probe.delivered()
    engine.decode_window(np.array([True, False]))            # round 1
    assert probe.delivered() - at_open == 2                  # not 4
    _admit(probe, engine, 1, "r1", asked["r1"])
    _admit(probe, engine, 0, "r2", asked["r2"])              # round 2
    engine.decode_window(np.array([True, True]))
    engine.decode_window(np.array([True, True]))             # round 3
    at_close = probe.delivered()
    assert at_close - at_open == 17

    ended_inside = [rid for rid in asked if probe.got[rid] >= asked[rid]]
    assert sum(asked[rid] for rid in ended_inside) == completed_inside

    engine.decode_window(np.array([False, True]))            # round 4
    assert probe.delivered() - at_close == (4 if b_asked == 13 else 0)
    # nothing is ever counted past what was asked
    assert probe.delivered() == sum(asked.values())
    assert all(probe.got[rid] >= n for rid, n in asked.items())


def test_a_request_not_yet_admitted_counts_nothing():
    probe, _ = _probe()
    probe.expect("r0", np.array([0, 0], np.int32), 5)
    assert probe.delivered() == 0
