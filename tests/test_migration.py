"""Live cross-shard KV page migration over compression-aware UCIe (PR 9).

The sharded engine can now re-home a live slot by MOVING its physical pages
between device-local pool partitions (gather → all_gather → scatter under
shard_map) instead of re-prefilling, with the transfer priced through the
SAME `core/ucie.transfer` closed form the time-stepped simulator drains.
These tests pin:

  * mid-decode migration is TOKEN-EXACT vs a stay-put twin across
    dense/moe/mla × {f32, int8} — the data path moves pool-native bytes
    (an int8 pool's int8 rows + bf16 scales ARE its block-compressed wire
    format), so migrated streams are bit-identical;
  * drain-via-migration emits the same tokens as drain-via-replay AND the
    fault-free twin, at ZERO extra prefill chunks (the O(bytes) vs O(FLOPs)
    claim), with exact pool accounting on both shards after every move;
  * refcounted shared/COW pages migrate intact: the mover gets fresh
    copies, the stayer keeps the originals;
  * an 8-device chaos run (deaths + sensor storms + squeezes) with
    migration on keeps token divergence at zero;
  * elastic rebalancing moves load back onto a rejoined shard without
    changing any token, and starvation rescue admits a page-starved head
    with fewer preemptions;
  * hot prefix pages replicate across shards over the same move primitive;
  * identical prompts submitted together coalesce (in-flight dedup);
  * the serving stack owns NO link math: `ucie.migration_ticks` /
    `ucie.transfer` is the single call path shared with the simulator.
"""

import inspect
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.launch.mesh import make_serve_mesh
from repro.serve.faults import FaultEvent, FaultPlan, chaos_plan
from repro.serve.sharded import ShardedServeEngine

mesh4 = make_serve_mesh(4)

def prompt(seed, n, vocab=512):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, vocab), np.int32)

def build(arch, **exec_kw):
    cfg = get_config(arch).smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32,
                                         **exec_kw))
    return model, model.init(jax.random.key(1))

def run_traffic(eng, lens, max_new=4, migrate_after=None):
    # optional mid-decode migration: after `migrate_after` ticks pick the
    # first active slot and re-home it to the scheduler's target shard,
    # asserting exact accounting on BOTH shards right after the move
    reqs = [eng.submit(prompt(i, n), max_new_tokens=max_new, seed=100 + i)
            for i, n in enumerate(lens)]
    moved = 0
    ticks = 0
    while (eng._sched.queue or any(r is not None for r in eng._slots)) \
            and ticks < 400:
        eng.step()
        ticks += 1
        if migrate_after is not None and ticks >= migrate_after \
                and moved == 0:
            live = [g for g in range(eng.n_slots) if eng._active[g]]
            for g in live:
                shard, slot = divmod(g, eng.slots_per_shard)
                dst = eng._sched.migration_target(shard, slot)
                if dst is not None:
                    eng._migrate_slot(shard, slot, dst)
                    eng.assert_pool_accounting()
                    eng.assert_local_page_tables()
                    moved += 1
                    break
    assert all(r.done for r in reqs)
    eng.assert_pool_accounting()
    return reqs, moved
"""


def _run(script: str):
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", _PRELUDE + script], env=env,
                       capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


def test_migration_exactness_dense_moe_8dev():
    """Mid-decode migration vs stay-put twin: dense/moe × {f32, int8}.
    The migrated stream must be bit-identical — pool-native byte moves
    cannot perturb schedule-independent KV rounding."""
    out = _run(r"""
for arch, kw in (("smollm-360m", {}),
                 ("smollm-360m", {"wdtype": "int8", "kv_dtype": "int8"}),
                 ("qwen2-moe-a2.7b", {}),
                 ("qwen2-moe-a2.7b", {"wdtype": "int8", "kv_dtype": "int8"})):
    model, params = build(arch)
    lens = [9, 17, 6]
    def eng():
        return ShardedServeEngine(model, mesh=mesh4, n_slots=8, max_len=64,
                                  params=params, page_size=8, **kw)
    stay, _ = run_traffic(eng(), lens)
    roam, moved = run_traffic(eng(), lens, migrate_after=2)
    assert moved == 1, (arch, kw, moved)
    for a, b in zip(stay, roam):
        assert a.out_tokens == b.out_tokens, (arch, kw, a.rid,
                                              a.out_tokens, b.out_tokens)
    print("OK", arch, kw.get("kv_dtype", "f32"))
print("MATRIX_DM_OK")
""")
    assert "MATRIX_DM_OK" in out, out[-2000:]


def test_migration_exactness_mla_8dev():
    """Mid-decode migration on the MLA latent-KV pool (deepseek-v2-lite:
    moe family + attn_kind='mla') × {f32, int8}: the latent rows move as
    pool-native bytes like any other pool entry."""
    out = _run(r"""
for kw in ({}, {"wdtype": "int8", "kv_dtype": "int8"}):
    model, params = build("deepseek-v2-lite")
    lens = [9, 17]
    def eng():
        return ShardedServeEngine(model, mesh=mesh4, n_slots=8, max_len=64,
                                  params=params, page_size=8, **kw)
    stay, _ = run_traffic(eng(), lens, max_new=3)
    roam, moved = run_traffic(eng(), lens, max_new=3, migrate_after=2)
    assert moved == 1, (kw, moved)
    for a, b in zip(stay, roam):
        assert a.out_tokens == b.out_tokens, (a.out_tokens, b.out_tokens)
    print("OK mla", kw.get("kv_dtype", "f32"))
print("MATRIX_MLA_OK")
""")
    assert "MATRIX_MLA_OK" in out, out[-2000:]


def test_drain_migration_vs_replay_8dev():
    """A sensor-driven DRAINING shard re-homes its live slots by page moves:
    tokens identical to BOTH the replay path and the fault-free twin, and —
    the O(bytes) vs O(FLOPs) point — at ZERO extra prefill chunks, where
    replay recomputes every displaced prompt."""
    out = _run(r"""
model, params = build("smollm-360m")
plan = FaultPlan(events=(
    FaultEvent(tick=4, kind="sensor_hot", shard=1, delta_c=60.0, ticks=8),))
lens = [5 + (i * 7) % 23 for i in range(5)]
runs = []
for p, mig in ((None, True), (plan, True), (plan, False)):
    eng = ShardedServeEngine(model, mesh=mesh4, n_slots=8, max_len=64,
                             params=params, page_size=8, n_pages=24,
                             fault_plan=p, migration=mig)
    reqs = [eng.submit(prompt(i, n), max_new_tokens=12, seed=100 + i)
            for i, n in enumerate(lens)]
    eng.run_to_completion()
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    runs.append((eng, reqs))
(free, fr), (mig, mr), (rep, rr) = runs
for a, b, c in zip(fr, mr, rr):
    assert a.out_tokens == b.out_tokens == c.out_tokens, \
        (a.rid, a.out_tokens, b.out_tokens, c.out_tokens)
st = mig.stats
assert st.migrations >= 1 and st.migrated_pages >= 1, st.summary()
assert st.migrated_bytes_compressed > 0
assert st.recoveries >= 1                       # drain displaced work
assert st.recovery_ticks_sum >= st.recoveries   # link latency was charged
# zero re-prefilled chunks: the migration run prefills EXACTLY what the
# fault-free twin does, while replay recomputes the displaced prompts
assert st.prefill_chunks == free.stats.prefill_chunks, \
    (st.prefill_chunks, free.stats.prefill_chunks)
assert rep.stats.prefill_chunks > free.stats.prefill_chunks
assert rep.stats.migrations == 0
print("DRAIN_MIG_OK", st.migrations, st.migrated_pages)
""")
    assert "DRAIN_MIG_OK" in out, out[-2000:]


def test_migration_shared_cow_pages_8dev():
    """Refcounted prefix-shared pages migrate intact: the moving slot gets
    fresh physical copies on the destination, the staying sharer keeps the
    originals (ref drops by one, never corrupts), and both streams stay
    exact. Accounting is asserted on both shards right after the move."""
    out = _run(r"""
model, params = build("smollm-360m")
sysp = prompt(0, 16)

def traffic(eng, migrate):
    r0 = eng.submit(sysp.copy(), max_new_tokens=2)
    eng.run_to_completion()           # registers the 2-page prefix
    tails = [prompt(9, 5), prompt(10, 7)]
    rs = [eng.submit(np.concatenate([sysp, t]), max_new_tokens=10,
                     seed=50 + i) for i, t in enumerate(tails)]
    moved = 0
    for _ in range(200):
        eng.step()
        if migrate and not moved:
            # both sharers decode on the prefix home shard; move ONE
            for g in range(eng.n_slots):
                if eng._active[g] and eng._slots[g] in rs:
                    shard, slot = divmod(g, eng.slots_per_shard)
                    s = eng._sched.shards[shard]
                    if not any(s.ref[p] > 1
                               for p in s.slot_pages[slot].values()):
                        continue      # wait for a genuinely shared mapping
                    dst = eng._sched.migration_target(shard, slot)
                    if dst is not None:
                        eng._migrate_slot(shard, slot, dst)
                        eng.assert_pool_accounting()
                        eng.assert_local_page_tables()
                        moved = 1
                        break
        if all(r.done for r in rs):
            break
    assert all(r.done for r in rs)
    eng.assert_pool_accounting()
    return [list(r.out_tokens) for r in rs], moved

def eng():
    return ShardedServeEngine(model, mesh=mesh4, n_slots=8, max_len=64,
                              params=params, page_size=8)
base, _ = traffic(eng(), migrate=False)
roam, moved = traffic(eng(), migrate=True)
assert moved == 1
assert base == roam, (base, roam)
print("COW_MIG_OK")
""")
    assert "COW_MIG_OK" in out, out[-2000:]


def test_chaos_with_migration_8dev():
    """Full chaos geometry — deaths, rejoins, squeezes AND sensor storms —
    on an 8-shard mesh with migration on: token divergence vs the
    fault-free twin stays ZERO, and the sensor-driven drains actually take
    the migration path (DEAD shards still replay: their bytes are gone)."""
    out = _run(r"""
mesh8 = make_serve_mesh(8)
model, params = build("smollm-360m")
plan = chaos_plan(3, n_shards=8, n_ticks=48, deaths=1, death_dwell=12,
                  squeezes=2, squeeze_pages=6, squeeze_dwell=8,
                  sensor_storms=2, sensor_delta_c=60.0, sensor_ticks=8)
assert plan.counts()["sensor_hot"] >= 1
lens = [5 + (i * 7) % 23 for i in range(6)]
runs = []
for p in (None, plan):
    eng = ShardedServeEngine(model, mesh=mesh8, n_slots=8, max_len=64,
                             params=params, page_size=8, n_pages=16,
                             fault_plan=p)
    reqs = [eng.submit(prompt(i, n), max_new_tokens=12, seed=100 + i)
            for i, n in enumerate(lens)]
    eng.run_to_completion()
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    runs.append((eng, reqs))
(base, br), (eng, cr) = runs
div = sum(a.out_tokens != b.out_tokens for a, b in zip(br, cr))
assert div == 0, div
st = eng.stats
assert st.faults_injected >= 3, st.faults_injected
assert st.migrations >= 1, st.summary()     # a drain went over the link
assert st.recoveries >= 1
print("CHAOS_MIG_OK", st.migrations, st.recoveries)
""")
    assert "CHAOS_MIG_OK" in out, out[-2000:]


def test_rebalance_and_rescue_8dev():
    """Elastic rebalancing: after a drained shard rejoins empty, the
    busy-slot gap pulls live slots back onto it — occupancy imbalance drops
    and NO token changes. Starvation rescue: a page-starved queue head is
    admitted by migrating a victim away instead of preempting it (fewer
    preemptions, same tokens)."""
    out = _run(r"""
model, params = build("smollm-360m")

# -- rebalance: drain empties shard 0; with threshold=1 the post-rejoin
#    busy gap (2 vs 0) migrates work back
plan = FaultPlan(events=(
    FaultEvent(tick=4, kind="sensor_hot", shard=0, delta_c=60.0, ticks=8),))
lens = [9, 12, 15, 18, 11, 14]
out_toks, imb, rebal = {}, {}, {}
for thr in (0, 1):
    eng = ShardedServeEngine(model, mesh=mesh4, n_slots=8, max_len=96,
                             params=params, page_size=8, n_pages=36,
                             fault_plan=plan, rebalance_threshold=thr)
    reqs = [eng.submit(prompt(i, n), max_new_tokens=24, seed=100 + i)
            for i, n in enumerate(lens)]
    eng.run_to_completion()
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    out_toks[thr] = [list(r.out_tokens) for r in reqs]
    imb[thr] = eng.shard_summary()["occupancy_imbalance"]
    rebal[thr] = eng.stats.rebalance_events
assert out_toks[0] == out_toks[1], "rebalancing changed tokens"
assert rebal[0] == 0 and rebal[1] >= 1, rebal
assert imb[1] < imb[0], imb
assert imb[1] < 0.67, imb
print("REBALANCE_OK", rebal[1], round(imb[0], 3), "->", round(imb[1], 3))
""")
    assert "REBALANCE_OK" in out, out[-2000:]


def test_ucie_single_call_path():
    """The serving stack and the time-stepped simulator consume ONE link
    cost model: `core/ucie.transfer` (via `ucie.migration_ticks`). No
    serving module re-derives bandwidth/flit/latency math of its own, and
    the tick conversion is pinned numerically against transfer()."""
    from repro.core import soc, ucie
    from repro.serve import migration, scheduler, sharded

    # the ONE coupling point exists and routes through transfer()
    mig_src = inspect.getsource(migration)
    assert "ucie.migration_ticks(" in mig_src
    tick_src = inspect.getsource(ucie.migration_ticks)
    assert "transfer(" in tick_src
    # the simulator drains through the same closed form
    assert "ucie_mod.transfer(" in inspect.getsource(soc)
    # no serving module owns link math — enforced by contract rule R1
    # (analysis/contracts): link fields, wire constants, hard-coded
    # bandwidth numbers and direct transfer() calls outside the sanctioned
    # migration_cost wrapper are all findings
    import pathlib

    from repro.analysis.contracts import run_rules

    repo_root = pathlib.Path(__file__).resolve().parents[1]
    findings = run_rules(repo_root, rules=["R1"])
    assert findings == [], "\n".join(str(f) for f in findings)
    del scheduler, sharded  # imported to prove the modules still load
    # numeric pin: ticks == ceil(transfer_time_us / tick_us), never 0
    cfg = ucie.UCIeConfig()
    for payload, tick_us in ((4096.0, 1000.0), (262144.0, 50.0),
                             (1.0, 1000.0)):
        t_us, _, _ = ucie.transfer(payload, cfg)
        want = max(1, int(-(-float(t_us) // tick_us)))
        got = ucie.migration_ticks(payload, cfg, tick_us=tick_us)
        assert got == want, (payload, tick_us, got, want)
    # compressed wire bytes are what migration accounts
    ticks, wire = migration.migration_cost(
        4096.0, migration.MigrationConfig())
    _, _, want_wire = ucie.transfer(4096.0, cfg)
    assert ticks >= 1 and wire == float(want_wire)


def test_inflight_prefix_dedup_single_host():
    """Identical prompts submitted together coalesce: the second holds at
    admission while the first prefills, then rides its registered pages —
    the PAIR costs exactly one cold prefill's chunks. The claim dies with
    its owner (cancel mid-prefill ⇒ the twin proceeds alone)."""
    import jax
    from repro.configs import get_config
    from repro.models import ExecOptions, build_model
    from repro.serve.engine import ServeEngine

    cfg = get_config("smollm-360m").smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(1))
    pr = np.asarray(jax.random.randint(
        jax.random.key(0), (32,), 0, 512), np.int32)

    def eng():
        return ServeEngine(model, n_slots=4, max_len=64, params=params,
                           page_size=8, prefix_cache=True)

    solo = eng()
    sr = solo.submit(pr.copy(), max_new_tokens=4)
    solo.run_to_completion()

    pair = eng()
    a = pair.submit(pr.copy(), max_new_tokens=4)
    b = pair.submit(pr.copy(), max_new_tokens=4)
    pair.run_to_completion()
    pair.assert_accounting()
    assert a.out_tokens == b.out_tokens
    st = pair.stats
    # the deferred twin full-hits (shared run + COW tail): zero extra chunks
    assert st.prefill_chunks == solo.stats.prefill_chunks, \
        (st.prefill_chunks, solo.stats.prefill_chunks)
    assert st.prefix_hits == 1 and st.prefix_hit_tokens >= 32, \
        (st.prefix_hits, st.prefix_hit_tokens)
    assert not pair._pending_digest and not pair._pending_by_rid

    # owner cancelled mid-prefill: the claim clears, the twin prefills
    canc = eng()
    a = canc.submit(pr.copy(), max_new_tokens=4)
    b = canc.submit(pr.copy(), max_new_tokens=4)
    canc.step()
    canc.cancel(a)
    canc.run_to_completion()
    canc.assert_accounting()
    assert b.done and not b.timed_out
    assert b.out_tokens == sr.out_tokens, (b.out_tokens, sr.out_tokens)
    assert not canc._pending_digest and not canc._pending_by_rid


def test_prefix_replication_8dev():
    """Cross-shard prefix reuse: when the hot-prefix home shard is full,
    the registered pages replicate to an admitting shard over the move
    primitive — the new request hits the cache THERE (no re-prefill) and
    its tokens match a cold twin's exactly."""
    out = _run(r"""
model, params = build("smollm-360m")
sysp = prompt(0, 16)            # 2 full pages of shared prefix

def traffic(eng):
    r0 = eng.submit(sysp.copy(), max_new_tokens=2)
    eng.run_to_completion()     # register on the home shard
    # two same-prefix admissions make the prefix HOT (min_prefix_hits=2)
    # and pin BOTH home-shard slots with long decodes
    rs = [eng.submit(np.concatenate([sysp, prompt(9 + i, 5 + i)]),
                     max_new_tokens=40, seed=50 + i) for i in range(2)]
    for _ in range(6):
        eng.step()
    # home shard full ⇒ the next same-prefix head must admit elsewhere
    r3 = eng.submit(np.concatenate([sysp, prompt(20, 6)]),
                    max_new_tokens=6, seed=70)
    eng.run_to_completion()
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    return r3, eng

r3, eng = traffic(ShardedServeEngine(model, mesh=mesh4, n_slots=8,
                                     max_len=96, params=params, page_size=8))
assert eng.stats.migrated_pages >= 2, eng.stats.summary()   # pages flew
assert r3.cached_prompt_tokens >= 16, r3.cached_prompt_tokens

# replication off: same traffic, same tokens, but the prefix re-prefills
r3_off, eng_off = traffic(ShardedServeEngine(
    model, mesh=mesh4, n_slots=8, max_len=96, params=params, page_size=8,
    migration=False))
assert eng_off.stats.migrated_pages == 0
assert r3.out_tokens == r3_off.out_tokens, (r3.out_tokens, r3_off.out_tokens)
assert eng.stats.prefill_chunks < eng_off.stats.prefill_chunks, \
    (eng.stats.prefill_chunks, eng_off.stats.prefill_chunks)
print("REPLICATION_OK", eng.stats.migrated_pages)
""")
    assert "REPLICATION_OK" in out, out[-2000:]
