"""Host spans of the serving engine and the training loop, read back from
a real ``jax.profiler`` trace: every span at its layer boundary, nested as
the layers are, with the request ids and counts it carries as stats."""
import collections
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.registry import get_config
from repro.data.synthetic import lm_batches, markov_tokens
from repro.launch.loop import StragglerOracle, TrainLoop
from repro.launch.train import TrainConfig
from repro.models.model import init_model
from repro.serve import PagedCacheConfig, ServeEngine

PREFIXES = ("repro.", "bench.")


def record(trace_dir: str, body):
    """Run ``body`` under the profiler inside a ``bench.window`` span;
    returns its result and the spans of the trace."""
    jax.profiler.start_trace(trace_dir)
    try:
        with TraceAnnotation("bench.window"):
            out = body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    return out, host_spans(ProfileData.from_file(path))


def host_spans(pd):
    """Each ``repro.*`` and ``bench.*`` host event as (start, end, name,
    stats, parent): the parent is the innermost such event on the same
    thread that holds it, or None."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, e.end_ns, e.name, dict(e.stats))
                          for e in line.events
                          if e.name.startswith(PREFIXES)),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for e in evs:
                while stack and stack[-1][1] < e[1]:
                    stack.pop()
                out.append(e + (stack[-1][2] if stack else None,))
                stack.append(e)
    return out


def named(spans, name):
    return [s for s in spans if s[2] == name]


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, init_model(jax.random.PRNGKey(0), cfg, max_pos=64)


SERVE_PARENTS = {
    "repro.serve.submit": {"bench.window"},
    "repro.serve.step": {"bench.window"},
    "repro.serve.admit": {"repro.serve.step"},
    "repro.serve.schedule": {"repro.serve.admit", "repro.serve.step"},
    "repro.serve.prefill": {"repro.serve.admit"},
    "repro.serve.page_write": {"repro.serve.admit"},
    "repro.serve.suffix": {"repro.serve.admit"},
    "repro.serve.decode": {"repro.serve.step"},
    "repro.serve.retire": {"repro.serve.step"},
}


@pytest.mark.parametrize("prefix_cache", ["off", "on"])
def test_serve_spans(tmp_path, qwen, prefix_cache):
    cfg, params = qwen
    ccfg = PagedCacheConfig(num_slots=2, page_size=4, num_pages=24,
                            max_pages_per_seq=8)
    eng = ServeEngine(params, cfg, ccfg, superstep_k=4,
                      prefix_cache=prefix_cache)
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    # the second copy of p0 hits the prefix cache: a suffix feed
    prompts, budgets = [p0, p0.copy(), p1], [4, 3, 6]
    steps0 = eng.stats["decode_steps"]

    def serve():
        rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        eng.run()
        return rids

    rids, spans = record(str(tmp_path / "trace"), serve)

    want = set(SERVE_PARENTS) - ({"repro.serve.suffix"}
                                 if prefix_cache == "off" else set())
    assert {s[2] for s in spans if s[2].startswith("repro.")} == want
    for s in spans:
        if s[2] in SERVE_PARENTS:
            assert s[4] in SERVE_PARENTS[s[2]], s
    submits = collections.Counter(s[3]["rid"] for s in
                                  named(spans, "repro.serve.submit"))
    assert submits == collections.Counter(rids)
    served = set()
    for s in named(spans, "repro.serve.prefill"):
        served |= {int(r) for r in str(s[3]["rids"]).split(";")}
        assert s[3]["tokens"] > 0
    served |= {s[3]["rid"] for s in named(spans, "repro.serve.page_write")}
    assert served == set(rids)
    assert sum(s[3]["n"] for s in named(spans, "repro.serve.admit")) == 3
    assert sum(s[3]["k"] for s in named(spans, "repro.serve.decode")) == \
        eng.stats["decode_steps"] - steps0
    assert all(s[3]["active"] >= 1
               for s in named(spans, "repro.serve.decode"))
    if prefix_cache == "on":
        (suffix,) = named(spans, "repro.serve.suffix")
        assert suffix[3]["rid"] == rids[1] and suffix[3]["tokens"] >= 1


def test_train_spans(tmp_path):
    cfg = get_config("qwen2-0.5b").reduced()
    tokens = markov_tokens(20_000, vocab=cfg.vocab_size, seed=0)
    tc = TrainConfig(mode="masked", lr=3e-3, remat_policy="none")
    loop = TrainLoop(cfg, tc, lm_batches(tokens, 8, 32, seed=1), n_agents=4,
                     r=1, oracle=StragglerOracle(4, 1, seed=0),
                     ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2,
                     max_pos=64)
    _, spans = record(str(tmp_path / "trace"), lambda: loop.run(3))

    steps = named(spans, "repro.train.step")
    assert [s[4] for s in steps] == ["bench.window"] * 3
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2]
    for name, count in [("select", 3), ("feed", 3), ("dispatch", 3),
                        ("sync", 3), ("ckpt", 1)]:
        evs = named(spans, "repro.train." + name)
        assert len(evs) == count, name
        assert {s[4] for s in evs} == {"repro.train.step"}, name
