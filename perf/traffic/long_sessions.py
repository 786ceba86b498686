"""Closed-loop long-context decode sessions, one session a slot for the whole run: a
slot opens its session in the lead-in, sends a long prompt in CHUNKS (each call
continues the session where the last one ended), then takes tokens one at a time
until the window ends. No session opens or ends inside the window, so the window
holds no prefill; what it times is every step at a long context.

Parameters: `processes` x `slots_per_process` slots; `prompt_lengths` with
`prompt_weights`; `chunk` (positions a call of the prompt); `answer_cap` (a session
stops at that many tokens: no session reaches the server's cache limit);
`lead_seconds` (the slots start that long before the window, uncounted: long enough
for every prompt). Every seed gives the SAME multiset of prompt lengths — in exact
proportion to their weights — dealt to the slots in another order, so that the
seed does not change the amount of work. A slot whose prompt has not finished when
the window opens is a FAILED session: the lead-in was too short for this server."""

from __future__ import annotations

import random
import time
from typing import Any, Dict, List

SERVER_PATH = "decode"  # which of the server's paths this traffic takes: warmed and checked, no other
TOKENS_AHEAD = 256  # answer inputs drawn at a time


def sizes(params: Dict[str, Any]) -> List[int]:
    """The fixed multiset of prompt lengths of one schedule, one a slot."""
    slots = params["processes"] * params["slots_per_process"]
    prompts: List[int] = []
    for length, weight in zip(params["prompt_lengths"], params["prompt_weights"]):
        prompts += [length] * round(weight * slots)
    return (prompts + [params["prompt_lengths"][0]] * slots)[:slots]


def schedule(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    prompts = sizes(params)
    rng = random.Random(int(seed))
    rng.shuffle(prompts)
    slots = [[[prompt, params["chunk"], params["answer_cap"], rng.randrange(2**31)]] for prompt in prompts]
    n = params["slots_per_process"]
    return {"processes": [slots[i * n:(i + 1) * n] for i in range(params["processes"])]}


def drive_slot(pipe, plan: List[List[int]], ctx: Dict[str, Any], out: Dict[str, Any]) -> None:
    """One slot's one session. Only what completes inside the window (`begin`..`end`,
    time.monotonic) is counted. Appends to `out`: token_gap_ms samples, tokens,
    attempted / completed / failed sessions, and `prefill_s` (the whole prompt's
    seconds, lead-in, for the log)."""
    import numpy as np

    from perf.runtime import float16_exact

    begin, end, hidden = ctx["begin"], ctx["end"], ctx["hidden"]
    [[prompt_len, chunk, answer_cap, stream_seed]] = plan
    session = f"{ctx['tag']}n0"
    rng = np.random.default_rng(stream_seed)
    draw = lambda positions: float16_exact(rng.standard_normal((1, positions, hidden), dtype=np.float32))
    try:
        opened = time.monotonic()
        for start in range(0, prompt_len, chunk):
            y = pipe.decode_step(draw(min(chunk, prompt_len - start)), session, reset=start == 0)
        last = time.monotonic()
        out["prefill_s"].append(last - opened)
        healthy = bool(np.isfinite(y[:, -1]).all())
        if last > begin:
            raise TimeoutError(f"the prompt of {prompt_len} positions finished {last - begin:.1f} s after the window opened")
        out["attempted"] += 1
        taken = 1
        while taken < answer_cap and time.monotonic() < end:
            for x in draw(min(TOKENS_AHEAD, answer_cap - taken))[0]:
                if time.monotonic() >= end:
                    break
                y = pipe.decode_step(x[None, None], session)
                now = time.monotonic()
                if begin <= now <= end:
                    out["token_gap_ms"].append(1000.0 * (now - last))
                    out["tokens"] += 1
                last, taken = now, taken + 1
                healthy = healthy and bool(np.isfinite(y).all())
        if not healthy:
            raise FloatingPointError(f"session {session} returned a non-finite position")
        out["completed"] += 1  # it ran to the window's end (or to its cap) without a fault
    except Exception as e:  # a session that raised, was shed or evicted, or whose prompt came late, counts as failed
        out["attempted"] = max(out["attempted"], 1)
        out["failed"] += 1
        out["errors"].append(repr(e)[:200])
    finally:
        pipe.close_decode_session(session)


def new_result() -> Dict[str, Any]:
    return {"token_gap_ms": [], "prefill_s": [], "tokens": 0, "attempted": 0, "failed": 0, "completed": 0, "errors": []}
