"""What the serving engine records about itself: request lifecycle stamps,
host spans in the profiler's trace, named scopes in its compiled programs,
and the public handle on those programs."""

import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.serve.engine import ServeEngine

STAMPS = ("t_enqueue", "t_admitted", "t_prefill_start", "t_prefill_end",
          "t_first_token", "t_done")


@pytest.fixture(scope="module")
def smol():
    cfg = get_config("smollm-360m").smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 512, n, dtype=np.int32)


def _stamps(r):
    return [getattr(r, k) for k in STAMPS]


def _assert_ordered(r):
    got = _stamps(r)
    assert None not in got, dict(zip(STAMPS, got))
    assert got == sorted(got), dict(zip(STAMPS, got))


@pytest.mark.parametrize("engine_kw", [
    pytest.param({}, id="chunked"),
    pytest.param({"paged": False}, id="monolithic"),
])
def test_lifecycle_stamps_are_ordered(smol, engine_kw):
    """enqueue <= admitted <= prefill start <= prefill end <= first token
    <= done, for every request, on one monotonic clock — more requests
    than slots, so some wait in the queue."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params,
                      **engine_kw)
    reqs = [eng.submit(_prompt(i, n), max_new_tokens=m)
            for i, (n, m) in enumerate([(40, 3), (9, 5), (23, 2), (17, 4)])]
    eng.run_to_completion()
    for r in reqs:
        _assert_ordered(r)
    # the two that waited for a slot were admitted after a later tick
    assert reqs[2].t_admitted > reqs[0].t_admitted


def test_a_full_prefix_hit_runs_no_prefill(smol):
    """A prompt whose every page is cached goes live at admission: it has
    a prefill end but no prefill start, and no chunk runs for it."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params,
                      page_size=8)
    p = _prompt(3, 24)
    first = eng.submit(p, max_new_tokens=2)
    eng.run_to_completion()
    chunks = eng.stats.prefill_chunks
    again = eng.submit(p, max_new_tokens=2)
    eng.run_to_completion()
    assert again.cached_prompt_tokens == 24
    assert eng.stats.prefill_chunks == chunks
    assert again.t_prefill_start is None
    assert again.t_admitted <= again.t_prefill_end <= again.t_first_token
    _assert_ordered(first)


def test_a_preempted_request_keeps_its_first_stamps(smol):
    """Preempted after its first token, a request re-admits and
    re-prefills its prompt and emitted tokens; its stamps stay those of
    its first pass, as `t_first_token` always has."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params)
    old = eng.submit(_prompt(5, 20), max_new_tokens=8)
    young = eng.submit(_prompt(6, 12), max_new_tokens=8)
    while len(young.out_tokens) < 2:
        eng.step()
    first = {k: getattr(old, k) for k in STAMPS[:5]}
    chunks = eng.stats.prefill_chunks
    # preempt the older request as `_preempt_once` preempts its victim:
    # release its slot and requeue it in age order
    slot = eng._slots.index(old)
    old.preemptions += 1
    eng._release(slot)
    eng._requeue(old)
    eng.run_to_completion()
    assert old.done and len(old.out_tokens) == 8
    assert eng.stats.prefill_chunks > chunks           # it prefilled again
    assert {k: getattr(old, k) for k in STAMPS[:5]} == first
    _assert_ordered(old)
    _assert_ordered(young)


def _host_events(log_dir):
    """(name, start, end, stats) of every event on the trace's host
    planes."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _inside(ev, outer):
    return any(o[1] <= ev[1] and ev[2] <= o[2] for o in outer)


def test_engine_spans_in_the_profiler_trace(smol, tmp_path):
    """Every engine span sits inside an `engine.step`; one admit_request
    per admission inside `engine.admit`; the chunk spans of a request
    tile its prompt, in steps of the chunk size."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params,
                      page_size=8, chunk_pages=2)
    lens = [40, 9, 23]
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [eng.submit(_prompt(10 + i, n), max_new_tokens=3)
                for i, n in enumerate(lens)]
        eng.run_to_completion()
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(tmp_path)
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    steps = by["engine.step"]
    assert [e[3]["tick"] for e in steps] == list(range(1, len(steps) + 1))
    for name in ("engine.admit", "engine.prefill_chunk", "engine.decode",
                 "engine.readback", "engine.release"):
        assert by[name], name
        assert all(_inside(e, steps) for e in by[name]), name
    admitted = by["engine.admit_request"]
    assert all(_inside(e, by["engine.admit"]) for e in admitted)
    assert sorted(e[3]["rid"] for e in admitted) == [r.rid for r in reqs]
    for e in admitted:
        r = next(q for q in reqs if q.rid == e[3]["rid"])
        assert e[3]["prompt_tokens"] == len(r.prompt)
        assert e[3]["cached_tokens"] == 0
    assert sorted(e[3]["rid"] for e in by["engine.release"]) == \
        [r.rid for r in reqs]
    assert all(1 <= e[3]["active"] <= 2 for e in by["engine.decode"])
    for r, n in zip(reqs, lens):
        chunks = sorted((e[3]["start"], e[3]["length"])
                        for e in by["engine.prefill_chunk"]
                        if e[3]["rid"] == r.rid)
        assert sum(length for _, length in chunks) == n
        assert [s for s, _ in chunks] == list(
            range(0, n, eng.chunk_tokens))


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_named_scopes_in_the_compiled_programs(smol):
    """The decode program's ops carry kv_pool, attn, mlp and lm_head in
    their op_name; the chunk program's carry all but lm_head. Lowered
    through the engine's public handle on its programs."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params)
    progs = eng.programs()
    assert set(progs) == {"decode", "chunk"}
    want = {"decode": ("kv_pool", "attn", "mlp", "lm_head"),
            "chunk": ("kv_pool", "attn", "mlp")}
    for name, (fn, args) in progs.items():
        names = _op_names(fn.lower(*args).compile().as_text())
        for scope in want[name]:
            assert any(f"/{scope}/" in n for n in names), (name, scope)
    assert eng.stats.decode_compiles == 1 and eng.stats.chunk_compiles == 1


def test_program_handle_matches_the_tick_loop(smol):
    """The handle's arguments are what `step()` feeds: after serving, the
    programs it names hit the jit cache the loop filled (no new trace)."""
    _, model, params = smol
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params)
    eng.submit(_prompt(1, 20), max_new_tokens=3)
    eng.run_to_completion()
    before = (eng.stats.decode_compiles, eng.stats.chunk_compiles)
    for fn, args in eng.programs().values():
        fn.lower(*args)
    assert (eng.stats.decode_compiles, eng.stats.chunk_compiles) == before
    dense = ServeEngine(model, n_slots=2, max_len=64, params=params,
                        paged=False)
    assert set(dense.programs()) == {"decode"}
