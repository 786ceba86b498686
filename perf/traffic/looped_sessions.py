"""Closed-loop decode sessions of a LOOPED language model, one session a slot for the whole run:
`long_sessions` with the loop. The model's blocks run `passes` times a token, so a slot sends its
prompt, and then every token, through `passes` calls of `pipe.decode_step(.., loop_pass=u)`: each call
walks the span once on the caches of pass u, and what it returns goes through the model's final norm
`F` (here, on the client: numpy, float32, eps 1e-6, then rounded to what the float16 wire carries) and
back as the next pass's input. The data dependency that defines the model is kept whole: pass u+1 of a
token is not sent before pass u returned.

Parameters: `processes` x `slots_per_process` slots; `prompt_lengths` with `prompt_weights`; `chunk`
(positions a call of the prompt; at least the longest prompt where the blocks take no chunks);
`passes` (the configuration's `total_ut_steps`); `answer_cap` (a session stops at that many tokens: no
session reaches the server's cache limit); `lead_seconds` (the slots start that long before the window,
uncounted: long enough for every prompt's `passes` prefills). Every seed gives the SAME multiset of
prompt lengths, dealt to the slots in another order, and the scale of `F` that the run's clients and its
reference check share (`final_norm`, drawn from `norm_seed(seed)`).

A TOKEN is counted, and its gap sampled, when its LAST pass returns. `between_ms` samples the client's
own time from a pass's return to the next pass's send (the norm, the rounding, the call's way out): its
share of a token, which no server clock sees. A slot whose prompt has not finished when the window opens
is a FAILED session: the lead-in was too short for this server."""

from __future__ import annotations

import random
import time
from typing import Any, Dict, List

from perf.traffic.long_sessions import sizes

SERVER_PATH = "decode"  # which of the server's paths this traffic takes: warmed and checked, no other
TOKENS_AHEAD = 256  # answer inputs drawn at a time
RMS_EPS = 1e-6  # `F`'s epsilon: the configuration's rms_norm_eps (a test holds the two equal)


def norm_seed(seed: int) -> int:
    """The seed of `F`'s scale in a run of ``seed``: beside the blocks' own (`seed * 64 + index`), past any span's depth."""
    return (int(seed) * 64 + 63) % (2**31 - 1)


def final_norm(seed: int, hidden: int):
    """`F`'s learned scale, drawn around 1 (a trained norm's scales are neither 1 nor alike), float32."""
    import numpy as np

    return (1.0 + 0.1 * np.random.default_rng(int(seed)).standard_normal(hidden)).astype(np.float32)


def apply_final_norm(x, scale):
    """`F` on the client: RMS norm over the hidden axis in float32, then what the float16 wire carries."""
    import numpy as np

    from perf.runtime import float16_exact

    x = np.asarray(x, np.float32)
    return float16_exact(x / np.sqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * scale)


def schedule(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    prompts = sizes(params)
    rng = random.Random(int(seed))
    rng.shuffle(prompts)
    slots = [[[prompt, params["chunk"], params["answer_cap"], rng.randrange(2**31), params["passes"], norm_seed(seed)]] for prompt in prompts]
    n = params["slots_per_process"]
    return {"processes": [slots[i * n:(i + 1) * n] for i in range(params["processes"])]}


def drive_slot(pipe, plan: List[List[int]], ctx: Dict[str, Any], out: Dict[str, Any]) -> None:
    """One slot's one session. Only what completes inside the window (`begin`..`end`, time.monotonic) is
    counted. Appends to `out`: token_gap_ms and between_ms samples, tokens, attempted / completed / failed
    sessions, `prefill_s` (the whole prompt's seconds, every pass, lead-in, for the log) and `taken` (the
    tokens the session took in all, for the log: whether it reached its cap)."""
    import numpy as np

    from perf.runtime import float16_exact

    begin, end, hidden = ctx["begin"], ctx["end"], ctx["hidden"]
    [[prompt_len, chunk, answer_cap, stream_seed, passes, scale_seed]] = plan
    session = f"{ctx['tag']}n0"
    rng = np.random.default_rng(stream_seed)
    scale = final_norm(scale_seed, hidden)
    draw = lambda positions: float16_exact(rng.standard_normal((1, positions, hidden), dtype=np.float32))

    try:
        opened = time.monotonic()
        for start in range(0, prompt_len, chunk):
            y = draw(min(chunk, prompt_len - start))
            for u in range(passes):  # the chunk through the loop: `passes` walks of the span, `F` between them
                y = apply_final_norm(pipe.decode_step(y, session, reset=start == 0, loop_pass=u), scale)
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
                x, between = x[None, None], []
                for u in range(passes):
                    sent = time.monotonic()
                    if u:
                        between.append(1000.0 * (sent - returned))
                    y = pipe.decode_step(x, session, loop_pass=u)
                    returned = time.monotonic()
                    x = apply_final_norm(y, scale)
                now = returned
                if begin <= now <= end:
                    out["token_gap_ms"].append(1000.0 * (now - last))
                    out["between_ms"].extend(between)
                    out["tokens"] += 1
                last, taken = now, taken + 1
                healthy = healthy and bool(np.isfinite(x).all())
        out["taken"].append(taken)
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
    return {"token_gap_ms": [], "between_ms": [], "prefill_s": [], "taken": [], "tokens": 0, "attempted": 0, "failed": 0,
            "completed": 0, "errors": []}
