"""Closed-loop decode sessions, as token-by-token generation is: each slot opens a
session, prefills a prompt, takes tokens one at a time, closes, and at once opens
the next.

Parameters: `processes` x `slots_per_process` slots; `prompt_lengths` with
`prompt_weights`; answers uniform on `answer_min`..`answer_max`; `sessions_per_slot`;
`lead_seconds` (the slots start that long before the window, uncounted).
Every seed gives the SAME multiset of (prompt, answer) sizes — prompts in exact
proportion to their weights, answers on an even grid — dealt to the slots in
another order, so that the seed does not change the amount of work."""

from __future__ import annotations

import random
import time
from typing import Any, Dict, List

SERVER_PATH = "decode"  # which of the server's paths this traffic takes: warmed and checked, no other


def sizes(params: Dict[str, Any]) -> List[List[int]]:
    """The fixed multiset of [prompt_len, answer_len] pairs of one schedule."""
    slots = params["processes"] * params["slots_per_process"]
    total = slots * params["sessions_per_slot"]
    prompts: List[int] = []
    for length, weight in zip(params["prompt_lengths"], params["prompt_weights"]):
        prompts += [length] * round(weight * total)
    prompts = (prompts + [params["prompt_lengths"][0]] * total)[:total]
    low, high = params["answer_min"], params["answer_max"]
    # answers on an even grid, paired with prompts through a fixed stride so that
    # every prompt length meets the whole range of answers
    answers = [low + (i * 37) % (high - low + 1) for i in range(total)]
    return [[p, a] for p, a in zip(prompts, answers)]


def schedule(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    pairs = sizes(params)
    rng = random.Random(int(seed))
    rng.shuffle(pairs)
    per_slot = params["sessions_per_slot"]
    slots = [
        [[p, a, rng.randrange(2**31)] for p, a in pairs[i * per_slot:(i + 1) * per_slot]]
        for i in range(params["processes"] * params["slots_per_process"])
    ]
    n = params["slots_per_process"]
    return {"processes": [slots[i * n:(i + 1) * n] for i in range(params["processes"])]}


def drive_slot(pipe, plan: List[List[int]], ctx: Dict[str, Any], out: Dict[str, Any]) -> None:
    """One slot's loop. The slot starts `lead` seconds before the window (`begin`..
    `end`, time.monotonic) so that the window opens on sessions in flight and not on
    every slot prefilling at once; its first session is cut to a share of its answer
    that grows with the slot's number, so that the sessions do not end in step.
    Only what completes inside the window is counted. Appends to `out`: ttft_ms and
    token_gap_ms samples, tokens, attempted / completed / failed sessions."""
    import numpy as np

    from perf.runtime import float16_exact

    begin, end, hidden = ctx["begin"], ctx["end"], ctx["hidden"]
    first_share = (ctx["slot"] + 1) / ctx["slots"]
    index = 0
    while time.monotonic() < end:
        prompt_len, answer_len, stream_seed = plan[index % len(plan)]
        if index == 0:
            answer_len = max(2, round(answer_len * first_share))
        session = f"{ctx['tag']}n{index}"
        index += 1
        rng = np.random.default_rng(stream_seed)
        prompt = float16_exact(rng.standard_normal((1, prompt_len, hidden), dtype=np.float32))
        steps = float16_exact(rng.standard_normal((answer_len - 1, 1, 1, hidden), dtype=np.float32))
        if time.monotonic() >= end:
            break
        seen = False  # a session counts as attempted once any part of it falls inside the window

        def inside(moment: float) -> bool:
            nonlocal seen
            if begin <= moment <= end:
                if not seen:
                    seen = True
                    out["attempted"] += 1
                return True
            return False

        try:
            opened = time.monotonic()
            y = pipe.decode_step(prompt, session, reset=True)
            last = time.monotonic()
            if inside(last):
                out["ttft_ms"].append(1000.0 * (last - opened))
                out["tokens"] += 1
            healthy = bool(np.isfinite(y[:, -1]).all())
            for x in steps:
                if time.monotonic() >= end:
                    break  # cut by the end of the window: neither completed nor failed
                y = pipe.decode_step(x, session)
                now = time.monotonic()
                if inside(now):
                    out["token_gap_ms"].append(1000.0 * (now - last))
                    out["tokens"] += 1
                last = now
                healthy = healthy and bool(np.isfinite(y).all())
            else:
                out["completed"] += seen
            if not healthy:
                raise FloatingPointError(f"session {session} returned a non-finite position")
        except Exception as e:  # a session that raised, was shed or evicted counts as failed
            if inside(time.monotonic()) or seen:
                out["failed"] += 1
                out["errors"].append(repr(e)[:200])
        finally:
            pipe.close_decode_session(session)


def new_result() -> Dict[str, Any]:
    return {"ttft_ms": [], "token_gap_ms": [], "tokens": 0, "attempted": 0, "failed": 0,
            "completed": 0, "errors": []}
