"""Traffic from a mix file and a seed.

A mix (``bench/mixes/<traffic>.json``) fixes the sizes: a set of prompt
lengths that ships with it, like a replayed prompt dataset, the output
budget's lognormal, shared system prompts, and either an open loop
(``rate_per_s``: Poisson arrivals) or a closed loop (``callers_per_slot``
callers per KV slot, each sending its next request when the last returns).

Every seed gets the same work: the sizes and the gaps between arrivals are
drawn once from the mix's ``base_seed``. The run's seed picks the tokens
(and reorders a closed loop's streams), so runs on different seeds differ
in content, not in how much there is to do.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

MIXES = pathlib.Path(__file__).resolve().parent / "mixes"


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # int32 tokens
    max_new: int
    due_s: float = 0.0          # offset from the window's start (open loop)
    shared: int = 0             # leading tokens shared with other requests


def load_mix(name: str) -> dict:
    return json.loads((MIXES / f"{name}.json").read_text())


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def system_prompts(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    sp = mix.get("system_prompts")
    if not sp:
        return []
    rng = _rng(seed, 1)
    return [rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
            for _ in range(sp["count"])]


def sizes(mix: dict, n: int) -> list[tuple[int, int, int]]:
    """``n`` (user prompt length, max_new, system prompt index or -1),
    from the mix's base seed alone."""
    rng = _rng(mix["base_seed"], 2)
    lens = rng.choice(np.asarray(mix["prompt_lengths"]), n)
    news = _lognormal(rng, mix["max_new"], n)
    sp = mix.get("system_prompts")
    if sp:
        w = np.asarray(sp["weights"], float)
        which = rng.choice(len(w), n, p=w / w.sum())
    else:
        which = np.full(n, -1)
    return [(int(a), int(b), int(c)) for a, b, c in zip(lens, news, which)]


def _build(spec, sys_prompts, vocab, rng) -> Request:
    n_user, max_new, which = spec
    user = rng.integers(0, vocab, n_user, dtype=np.int32)
    if which < 0:
        return Request(user, max_new)
    head = sys_prompts[which]
    return Request(np.concatenate([head, user]), max_new, shared=len(head))


def open_schedule(mix: dict, seconds: float, vocab: int,
                  seed: int) -> list[Request]:
    """Requests due in a window of ``seconds``: ``rate_per_s * seconds`` of
    them, with exponential gaps scaled to fill the window exactly. Sizes,
    gaps and their order come from the mix alone, like a replayed trace:
    with some tens of requests in a window, the order of long and short
    ones moves the tail more than anything a change to the program does,
    so the seed picks only the tokens."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    base = _rng(mix["base_seed"], 3)
    gaps = base.exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    rng = _rng(seed, 4)
    sp = system_prompts(mix, vocab, seed)
    out = []
    due = 0.0
    for gap, spec in zip(gaps[:n], sizes(mix, n)):
        due += gap
        req = _build(spec, sp, vocab, rng)
        req.due_s = due
        out.append(req)
    return out


def closed_streams(mix: dict, callers: int, vocab: int, seed: int,
                   per_caller: int) -> list[list[Request]]:
    """One stream of ``per_caller`` requests for each caller."""
    specs = sizes(mix, callers * per_caller)
    rng = _rng(seed, 5)
    specs = [specs[i] for i in rng.permutation(len(specs))]
    sp = system_prompts(mix, vocab, seed)
    return [[_build(s, sp, vocab, rng)
             for s in specs[c * per_caller:(c + 1) * per_caller]]
            for c in range(callers)]


def warm_requests(mix: dict, vocab: int, seed: int) -> list[list[Request]]:
    """Requests that compile every prefill shape the mix can ask for, in
    phases to send one after another (each phase's requests in order):

    1. each prompt length once as a full prefill (under a throwaway shared
       prefix where the mix has system prompts, so the full-prefill length
       is the one a first sight of a system prompt takes);
    2. with system prompts: each real system prompt once (registering it
       in the prefix cache), then each length once behind one of them, so
       the prefix-hit extend of every suffix length compiles too.
    Every request asks for one new token: decode windows are warmed by the
    engine itself."""
    rng = _rng(seed, 6)
    sp = system_prompts(mix, vocab, seed)
    lens = sorted(set(mix["prompt_lengths"]))
    if not sp:
        return [[_build((n, 1, -1), [], vocab, rng) for n in lens]]
    fake = [rng.integers(0, vocab, len(sp[0]), dtype=np.int32)]
    full = [_build((n, 1, 0), fake, vocab, rng) for n in lens]
    for r in full:                      # a fresh prefix each: no hits
        r.prompt[:len(sp[0])] = rng.integers(0, vocab, len(sp[0]))
    register = [_build((lens[0], 1, i), sp, vocab, rng)
                for i in range(len(sp))]
    extend = [_build((n, 1, i % len(sp)), sp, vocab, rng)
              for i, n in enumerate(lens)]
    return [full, register, extend]
