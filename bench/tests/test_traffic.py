"""Traffic generation and the end-to-end arithmetic, on the CPU."""

import os
import pathlib
import subprocess
import sys
import threading
import time
from concurrent import futures as cf

import numpy as np
import pytest

from bench import generator, run
from bench.metrics import latencies_ms

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 12345          # past 32 signed bits, as the driver's are


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_same_seed_same_schedule(mix):
    m = generator.load_mix(mix)
    a = generator.open_schedule(m, 20.0, 1000, SEED)
    b = generator.open_schedule(m, 20.0, 1000, SEED)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_every_seed_gets_the_same_work(mix):
    m = generator.load_mix(mix)
    a = generator.open_schedule(m, 20.0, 1000, SEED)
    b = generator.open_schedule(m, 20.0, 1000, SEED + 1)
    assert len(a) == len(b) == round(m["rate_per_s"] * 20.0)
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert max(r.due_s for r in a) < 20.0
    assert {len(r.prompt) - r.shared for r in a} <= set(m["prompt_lengths"])


def test_closed_streams_fixed_sizes():
    m = generator.load_mix("batch")
    a = generator.closed_streams(m, 4, 1000, SEED, 8)
    b = generator.closed_streams(m, 4, 1000, SEED + 1, 8)
    sizes = [sorted((len(r.prompt), r.max_new) for s in x for r in s)
             for x in (a, b)]
    assert sizes[0] == sizes[1] and len(a) == 4 and len(a[0]) == 8


def test_warm_requests_cover_every_prefill_shape():
    m = generator.load_mix("chat")
    full, register, extend = generator.warm_requests(m, 1000, SEED)
    sp = m["system_prompts"]["tokens"]
    lens = sorted(set(m["prompt_lengths"]))
    assert sorted(len(r.prompt) for r in full) == [sp + n for n in lens]
    assert len(register) == m["system_prompts"]["count"]
    assert sorted(len(r.prompt) - sp for r in extend) == lens


class FakeRouter:
    """Replies after ``service_s``; a stall holds every reply until it
    ends; ``block_first_s`` makes the first submit itself slow."""

    def __init__(self, service_s=0.01, stall=None, block_first_s=0.0):
        self.service_s, self.stall = service_s, stall
        self.block_first_s = block_first_s
        self.t0 = time.monotonic()
        self.futures = self
        self.calls = 0

    def submit(self, prompt, max_new):
        self.calls += 1
        if self.calls == 1 and self.block_first_s:
            time.sleep(self.block_first_s)
        fut = cf.Future()
        delay = self.service_s
        if self.stall is not None:
            now = time.monotonic() - self.t0
            lo, hi = self.stall
            if now + delay > lo and now < hi:
                delay = hi - now + self.service_s
        out = np.concatenate([prompt, np.ones(max_new, np.int32)])
        threading.Timer(delay, fut.set_result, (out,)).start()
        return fut


def _recs(n, seconds):
    return [run.Rec(np.zeros(4, np.int32), 8, 0, seconds * i / n)
            for i in range(n)]


def _open(router, n=40, seconds=1.0):
    recs = _recs(n, seconds)
    run.open_loop(router, recs, seconds, grace_s=5.0)
    r = run.Run(cell={}, model={}, mix={}, chips=1, num_slots=1, peaks={},
                window_s=seconds, requests=recs)
    return r


def test_open_loop_latency_is_timed_from_due():
    r = _open(FakeRouter(block_first_s=0.3))
    late = [q for q in r.requests if q.sent - q.due_s > 0.1]
    assert late, "the blocked send made later requests late"
    for q in r.requests:
        assert q.done - q.due_s >= q.sent - q.due_s + 0.009
    assert max(latencies_ms(r)) >= 300.0


def test_stall_raises_p90_and_lowers_tokens():
    from bench.metrics import latency_p90_ms, output_tokens_per_s
    calm = _open(FakeRouter())
    stalled = _open(FakeRouter(stall=(0.5, 1.5)))
    assert latency_p90_ms.read(stalled) > latency_p90_ms.read(calm) + 100
    assert output_tokens_per_s.read(stalled) < output_tokens_per_s.read(calm)

    def closed(router):
        recs = []
        streams = [_recs(50, 1.0) for _ in range(2)]
        run.closed_loop(router, streams, 1.0, recs)
        time.sleep(0.5)
        return run.Run(cell={}, model={}, mix={}, chips=1, num_slots=1,
                       peaks={}, window_s=1.0, requests=recs)

    fast = output_tokens_per_s.read(closed(FakeRouter(service_s=0.05)))
    slow = output_tokens_per_s.read(
        closed(FakeRouter(service_s=0.05, stall=(0.2, 0.8))))
    assert slow < 0.6 * fast


def test_compiles_in_window_counts_a_fresh_jit():
    import jax
    import jax.numpy as jnp
    counter = run.CompileCounter()
    f = jax.jit(lambda x: x * 3 + 1)
    a, b, c = jnp.ones(3), jnp.ones(5), jnp.ones(2)
    f(a)
    counter.armed = True
    f(a)                                 # cached: no compile
    assert counter.count == 0
    f(b)                                 # a new shape compiles
    jax.jit(lambda x: x - 7)(c)          # and so does a new function
    counter.armed = False
    assert counter.count == 2


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "correct" not in p.stdout
