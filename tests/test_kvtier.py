"""KV movement plane drills (ISSUE 13): host-RAM prefix-cache tier +
prefill->decode KV handoff.

Covers, in tiers of machinery:

- jax-free units: PageAllocator edges (block keys at exact page
  boundaries, single-page prompts, the parent-evicted-while-child-
  published cascade, republish-after-recycle) and the incremental
  ``n_evictable`` counter pinned against the scan; HostTier chain-node
  identity (never recycled), LRU cap, crc corruption; the kv_transfer
  wire format's reject-don't-install contract.
- engine drills: spill -> swap-in roundtrip with token-identical outputs,
  export/import handoff between two engines, ThreadedEngine.call.
- THE tier A/B: same seeded trace with a shared-prefix working set sized
  past the HBM page pool, host tier on vs off — strictly higher hit
  ratio (a count of reused tokens), eviction churn absorbed by host
  hits, perf_compare 0 on a row's copy / 1 on a degraded copy.
- THE handoff drill: prefill_heavy + decode_heavy fleet behind a real
  gateway — handoff-accepted requests decode without re-prefilling the
  shipped pages (reused tokens == shipped tokens on the PR 8 counters),
  and the cost model demonstrably declines short prompts (decision
  journal rows assert both branches taken).
- chaos: a killed/error'd handoff leg falls back to re-prefill with zero
  client-visible failures; a bit-flipped host-tier entry is detected by
  crc, dropped, counted, never served.
"""

from __future__ import annotations

import json
import os
import random
import threading
import urllib.request

import numpy as np
import pytest

from ditl_tpu import chaos
from ditl_tpu.chaos import FaultPlane
from ditl_tpu.infer.host_tier import HostTier
from ditl_tpu.infer.kv_transfer import (
    KVTransferError, deserialize_pages, serialize_pages,
)
from ditl_tpu.infer.paged_cache import PageAllocator, block_keys

pytestmark = pytest.mark.kvtier


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.disarm()


# -- PageAllocator edges (ISSUE 13 satellite) --------------------------------


def test_block_keys_page_size_exactly_divides():
    toks = list(range(32))
    keys = block_keys(toks, 16, parents=[7, 9])
    # 32 tokens at page 16: EXACTLY two full pages, no phantom third key.
    assert len(keys) == 2
    assert keys[0] == (0, tuple(range(16)))
    assert keys[1] == (7, tuple(range(16, 32)))


def test_block_keys_single_page_prompt():
    toks = list(range(16))
    assert block_keys(toks, 16, parents=[3]) == [(0, tuple(range(16)))]
    # One token short of a page: no full page, no keys.
    assert block_keys(toks[:15], 16, parents=[]) == []


def test_parent_evicted_while_child_published_cascades():
    alloc = PageAllocator(8)
    pages = alloc.alloc(3)
    toks = list(range(48))
    alloc.publish_chain(toks, 16, pages)
    # A live request still holds the CHILD (deepest page) but not the
    # parent chain — exactly the state a finished-parent/streaming-child
    # conversation leaves.
    alloc.retain(pages[2])
    for pid in pages:
        alloc.release(pid)
    # Exhaust the pool: eviction claims the LRU parent and must CASCADE
    # its published descendants (their keys chain through the recycled
    # physical id) — but the retained child's memory is NOT freed.
    got = alloc.alloc(6)
    assert pages[0] in got and pages[1] in got
    assert pages[2] not in got  # in-flight ref keeps the child's page
    # The whole chain is unmatchable now (no stale child key survived).
    assert alloc.match_prefix(toks + [1], 16) == []
    alloc.release(pages[2])
    assert alloc.n_evictable == alloc.scan_evictable()


def test_republish_after_recycle_verifies_content():
    alloc = PageAllocator(4)
    pages = alloc.alloc(3)
    alloc.publish_chain(list(range(32)), 16, pages[:2])
    for pid in pages:
        alloc.release(pid)
    # Force the recycle: the old chain is evicted, its physical ids reused
    # for DIFFERENT content, republished under new keys.
    fresh = alloc.alloc(2)
    assert set(fresh) & set(pages[:2])  # ids really recycled
    new_toks = list(range(100, 132))
    alloc.publish_chain(new_toks, 16, fresh)
    for pid in fresh:
        alloc.release(pid)
    # Old content must NOT match (the recycled id's key was cascaded out);
    # new content must match exactly.
    assert alloc.match_prefix(list(range(32)) + [1], 16) == []
    got = alloc.match_prefix(new_toks + [1], 16)
    assert len(got) == 2
    for pid in got:
        alloc.release(pid)
    assert alloc.n_evictable == alloc.scan_evictable()


def test_n_evictable_counter_equals_scan_randomized():
    rng = random.Random(13)
    alloc = PageAllocator(12)
    held: list[int] = []
    published = 0
    for step in range(300):
        op = rng.random()
        if op < 0.4 and len(held) < 8:
            try:
                held.extend(alloc.alloc(rng.randint(1, 2)))
            except MemoryError:
                pass
        elif op < 0.6 and len(held) >= 2:
            toks = [rng.randint(0, 50) for _ in range(32)]
            alloc.publish_chain(toks, 16, held[:2])
            published += 1
        elif op < 0.9 and held:
            alloc.release(held.pop(rng.randrange(len(held))))
        else:
            toks = [rng.randint(0, 50) for _ in range(33)]
            for pid in alloc.match_prefix(toks, 16):
                alloc.release(pid)
        assert alloc.n_evictable == alloc.scan_evictable(), (
            f"diverged at step {step}"
        )


def test_evicted_group_reports_chain_blocks():
    fired: list = []
    alloc = PageAllocator(5, on_evict=fired.append)
    pages = alloc.alloc(3)
    toks = list(range(48))
    alloc.publish_chain(toks, 16, pages)
    for pid in pages:
        alloc.release(pid)
    alloc.alloc(4)  # 1 free + eviction of the chain head, cascading all
    assert len(fired) == 1
    group = fired[0]
    # Parent-first, each with the exact token blocks from the root.
    assert [g[0] for g in group] == pages
    for depth, (_, root, blocks) in enumerate(group):
        assert root == 0
        assert blocks == tuple(
            tuple(toks[i * 16:(i + 1) * 16]) for i in range(depth + 1)
        )


# -- HostTier units ----------------------------------------------------------


def _page(v: float, shape=(2, 2, 16, 8)):
    return {"kp": np.full(shape, v, np.float32),
            "vp": np.full(shape, -v, np.float32)}


def test_host_tier_node_ids_never_recycled():
    t = HostTier(1 << 20)
    nid = t.intern(0, [(1, 2), (3, 4)])
    assert t.put(nid, _page(1.0))
    # Drop the entry (corruption path) — pruning frees the node chain.
    t.corrupt(nid)
    assert t.fetch(nid) is None
    # Re-interning the SAME chain must mint a strictly newer id: an entry
    # keyed by the old id can never verify against new content.
    nid2 = t.intern(0, [(1, 2), (3, 4)])
    assert nid2 > nid


def test_host_tier_lru_cap_and_oversize():
    page_bytes = sum(a.nbytes for a in _page(0.0).values())
    t = HostTier(page_bytes * 2 + 16)
    nids = [t.intern(0, [((i,) * 4)]) for i in range(3)]
    assert all(t.put(n, _page(float(i))) for i, n in enumerate(nids))
    # Cap holds two: the oldest was LRU-evicted.
    assert t.n_entries == 2 and t.evictions == 1
    assert t.fetch(nids[0]) is None
    got = t.fetch(nids[2])
    assert np.all(got["kp"] == 2.0)
    # An entry larger than the whole cap is refused, counted dropped.
    small = HostTier(16)
    nid = small.intern(0, [(9, 9)])
    assert not small.put(nid, _page(0.0))
    assert small.dropped == 1


def test_host_tier_put_on_pruned_node_refuses_not_raises():
    # A pending spill's node can be PRUNED before its put runs (its
    # descendant's entry evicted in the same batch walks pruning up
    # through entry-less ancestors): put must refuse and count, never
    # raise into the engine driver.
    page_bytes = sum(a.nbytes for a in _page(0.0).values())
    t = HostTier(page_bytes + 16)  # cap holds exactly one entry
    parent = t.intern(0, [(1,) * 4])
    child = t.intern(0, [(1,) * 4, (2,) * 4])
    assert t.put(child, _page(1.0))
    # Evict the child's entry (cap pressure from an unrelated chain):
    # pruning removes the child node AND the entry-less parent node.
    other = t.intern(0, [(9,) * 4])
    assert t.put(other, _page(2.0))
    assert not t.has_entry(child)
    # The parent's queued spill now lands on a pruned node: refused.
    dropped0 = t.dropped
    assert not t.put(parent, _page(3.0))
    assert t.dropped == dropped0 + 1


def test_host_tier_corrupt_detected_never_served():
    t = HostTier(1 << 20)
    nid = t.intern(-1, [(5, 6, 7)])  # adapter root namespacing
    assert t.put(nid, _page(3.0))
    assert t.corrupt(nid, bit=123)
    assert t.fetch(nid) is None  # detected + dropped, never served
    assert t.corrupt_dropped == 1
    assert not t.has_entry(nid)


# -- kv_transfer wire format -------------------------------------------------


def _blob():
    meta = {"page_size": 4, "blocks": [[1, 2, 3, 4], [5, 6, 7, 8]]}
    pages = [_page(float(i), shape=(2, 2, 4, 8)) for i in range(2)]
    return serialize_pages(meta, pages)


def test_kv_transfer_roundtrip():
    blob = _blob()
    meta, pages = deserialize_pages(blob)
    assert meta["n_pages"] == 2 and meta["page_size"] == 4
    assert np.all(pages[1]["kp"] == 1.0) and np.all(pages[1]["vp"] == -1.0)


def test_kv_transfer_bfloat16_roundtrip():
    # Extension dtypes ride the wire by NAME: ml_dtypes bfloat16's .str
    # is an opaque '<V2' that np.dtype() rebuilds as raw void — the
    # silent-corruption path this pin exists to keep closed.
    import ml_dtypes

    arr = np.arange(16, dtype=np.float32).astype(ml_dtypes.bfloat16)
    blob = serialize_pages(
        {"page_size": 4, "blocks": [[1, 2, 3, 4]]},
        [{"kp": arr.reshape(4, 4), "vp": arr.reshape(4, 4)}],
    )
    meta, pages = deserialize_pages(blob)
    assert meta["part_dtypes"]["kp"] == "bfloat16"
    assert pages[0]["kp"].dtype == np.dtype(ml_dtypes.bfloat16)
    assert np.array_equal(pages[0]["kp"], arr.reshape(4, 4))


def test_kv_transfer_rejects_bad_meta_tables():
    import struct
    import zlib

    def rewrite_meta(blob, mutate):
        (mlen,) = struct.unpack("<I", blob[8:12])
        meta = json.loads(blob[12:12 + mlen])
        mutate(meta)
        mbytes = json.dumps(meta, sort_keys=True).encode()
        return (blob[:8] + struct.pack("<I", len(mbytes)) + mbytes
                + struct.pack("<I", zlib.crc32(mbytes))
                + blob[12 + mlen + 4:])

    # crc-VALID blobs with missing/malformed dtype/shape tables must fail
    # as KVTransferError (the endpoint's 400 contract), never a KeyError
    # or a TypeError out of np.dtype on attacker-chosen strings.
    for mutate in (
        lambda m: m.pop("part_dtypes"),
        lambda m: m.pop("part_shapes"),
        lambda m: m["part_dtypes"].pop("kp"),
        lambda m: m["part_dtypes"].__setitem__("kp", "no_such_dtype"),
        lambda m: m["part_dtypes"].__setitem__("kp", 7),
        lambda m: m["part_shapes"].__setitem__("kp", "not-a-shape"),
        lambda m: m["part_shapes"].__setitem__("kp", [2, -1, 4]),
    ):
        with pytest.raises(KVTransferError):
            deserialize_pages(rewrite_meta(_blob(), mutate))


def test_perf_compare_gates_fallback_appearing():
    from ditl_tpu.telemetry.perf_compare import compare_records

    clean = {"schema": 1, "value": 100.0,
             "kv_handoff": {"schema": 1, "handoff_fallback_ratio": 0.0}}
    stormy = json.loads(json.dumps(clean))
    stormy["kv_handoff"]["handoff_fallback_ratio"] = 0.5
    # 0 -> >0 is a regression class of its own (the generic relative-delta
    # loop skips zero baselines, which would make the gate vacuous on
    # exactly the healthy case).
    code, report = compare_records(clean, stormy, 0.05)
    assert code == 1 and "handoff_fallback_ratio" in report
    code, _ = compare_records(clean, clean, 0.05)
    assert code == 0
    # A nonzero baseline gates through the ordinary direction rule.
    code, _ = compare_records(stormy, clean, 0.05)
    assert code == 0


def test_kv_transfer_rejects_torn_and_corrupt():
    blob = _blob()
    # Truncation at MANY offsets: header, meta, part length, part body,
    # trailing crc — every torn shape must reject, never partially parse.
    for cut in (4, 10, 40, len(blob) // 2, len(blob) - 1):
        with pytest.raises(KVTransferError):
            deserialize_pages(blob[:cut])
    # Any flipped bit must fail a crc (meta or part).
    for pos in (16, len(blob) // 2, len(blob) - 8):
        bad = bytearray(blob)
        bad[pos] ^= 0x10
        with pytest.raises(KVTransferError):
            deserialize_pages(bytes(bad))
    with pytest.raises(KVTransferError):
        deserialize_pages(b"NOPE" + blob[4:])
    with pytest.raises(KVTransferError):
        deserialize_pages(blob + b"trailing")


# -- engine drills -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=256, dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params, ByteTokenizer()


def _engine(tiny, **kw):
    from ditl_tpu.infer.continuous import ContinuousEngine

    cfg, params, tok = tiny
    kw.setdefault("n_slots", 1)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("cache_mode", "paged")
    kw.setdefault("page_size", 16)
    return ContinuousEngine(params, cfg, tok, **kw)


def _run_groups(eng, groups, rounds, max_new=4):
    """Submit each group's prompt ``rounds`` times, cycling groups within
    a round (so the tiny pool must evict between reuses); returns the
    ordered list of output token lists."""
    outs = []
    rid = 0
    for r in range(rounds):
        for p in groups:
            eng.submit(list(p), max_new_tokens=max_new, temperature=0.0,
                       seed=rid)
            rid += 1
            outs.extend(tokens for _, tokens in sorted(eng.run().items()))
    return outs


def test_spill_swap_in_roundtrip_token_identical(tiny):
    # 4 distinct 2-page prompts through a pool that holds ~1 of them:
    # every reuse round trips through the host tier. Outputs must be
    # TOKEN-IDENTICAL round over round — swapped-in KV is the same KV.
    groups = [[10 + g] * 33 for g in range(4)]
    eng = _engine(tiny, n_pages=5, host_tier_mb=4)
    outs = _run_groups(eng, groups, rounds=2)
    assert outs[:4] == outs[4:]
    m = eng.metrics
    assert m.prefix_cache_hit_tokens_by_tier["host"].value > 0
    assert m.host_tier_swap_in.count > 0
    assert eng.allocator.n_evictable == eng.allocator.scan_evictable()
    st = eng.stats()
    assert st["host_tier"]["spilled"] > 0
    assert st["host_tier"]["swapped_in"] > 0


def test_tier_ab_past_hbm_capacity_perf_compare_gated(tiny):
    # THE tier A/B (acceptance): same seeded trace, shared-prefix working
    # set (4 groups x 2 published pages + working pages) strictly larger
    # than the pool (4 usable pages), host tier OFF vs ON.
    from ditl_tpu.telemetry.perf_compare import compare_records
    from ditl_tpu.telemetry.serving import serving_bench_summary

    from ditl_tpu.telemetry.serving import snapshot_serving

    groups = [[20 + g] * 33 for g in range(4)]
    rows = {}
    outs = {}
    for leg, tier_mb in (("off", 0), ("on", 4)):
        eng = _engine(tiny, n_pages=5, host_tier_mb=tier_mb)
        # Warm-up rounds carry the compile walls (prefill programs, and on
        # the tier leg the first swap-in's install program); the gated
        # summary covers the driven region only (snapshot after warm-up).
        outs[leg] = _run_groups(eng, groups, rounds=2)
        base = snapshot_serving([eng.metrics])
        outs[leg] = _run_groups(eng, groups, rounds=2)
        summary = serving_bench_summary([eng.metrics], since=base)
        assert summary["ttft_p95_s"] is not None  # the instrument ran
        # The perf_compare gate below runs on the measured reuse
        # accounting; the wall-clock keys are taken out of its way.
        for key in list(summary):
            if key.endswith("ttft_p95_s") or key.endswith(
                    "interference_p95_s"):
                summary.pop(key)
        rows[leg] = {
            "schema": 1,
            "value": float(eng.metrics.tokens_generated.value),
            "serving": summary,
            "evictions": int(eng.metrics.prefix_cache_evictions.value),
            "host_hit_tokens":
                eng.metrics.prefix_cache_hit_tokens_by_tier["host"].value,
        }
    # Same seeded trace => token-identical outputs across the legs (the
    # tier changes WHERE KV comes from, never what it holds).
    assert outs["off"] == outs["on"]
    off_s, on_s = rows["off"]["serving"], rows["on"]["serving"]
    # Strictly higher TOTAL prefix-cache hit ratio with the tier on.
    assert on_s["prefix_cache_hit_ratio"] > off_s["prefix_cache_hit_ratio"]
    assert on_s["host_tier_hit_ratio"] > 0.0
    assert off_s["host_tier_hit_ratio"] == 0.0
    # Eviction churn visibly absorbed by host hits: both legs churned,
    # only the tier leg turned churn back into reuse.
    assert rows["on"]["evictions"] > 0
    assert rows["on"]["host_hit_tokens"] > 0
    assert rows["off"]["host_hit_tokens"] == 0
    # perf_compare gates the tier-on row: its own copy passes...
    code, report = compare_records(
        rows["on"], json.loads(json.dumps(rows["on"])), 0.05)
    assert code == 0, report
    # ...and a synthetically degraded copy of the tier-on row must FAIL
    # against it (the round-over-round regression the gate exists for:
    # the tier stopped absorbing churn).
    degraded = json.loads(json.dumps(rows["on"]))
    degraded["serving"]["prefix_cache_hit_ratio"] = round(
        on_s["prefix_cache_hit_ratio"] * 0.5, 4)
    degraded["serving"]["host_tier_hit_ratio"] = round(
        on_s["host_tier_hit_ratio"] * 0.5, 4)
    code, report = compare_records(rows["on"], degraded, 0.05)
    assert code == 1, report
    assert "host_tier_hit_ratio" in report or "prefix_cache_hit_ratio" \
        in report


def test_chaos_bit_flipped_host_entry_recovers(tiny):
    # A corrupt host entry must be detected by crc, dropped, counted —
    # and the request completes via re-prefill (zero client-visible
    # failures). Token-identical to the clean round pins correctness.
    groups = [[30 + g] * 33 for g in range(4)]
    eng = _engine(tiny, n_pages=5, host_tier_mb=4)
    clean = _run_groups(eng, groups, rounds=1)
    chaos.arm(FaultPlane(rules="kvtier.swap_in:corrupt@max=1"))
    again = _run_groups(eng, groups, rounds=1)
    assert again == clean
    assert eng.metrics.host_tier_corrupt_entries.value == 1
    assert eng.host_tier.corrupt_dropped == 1


def test_chaos_spill_error_drops_batch_counted(tiny):
    groups = [[40 + g] * 33 for g in range(3)]
    eng = _engine(tiny, n_pages=5, host_tier_mb=4)
    chaos.arm(FaultPlane(rules="kvtier.spill:error@max=1"))
    _run_groups(eng, groups, rounds=1)
    assert eng.metrics.host_tier_dropped_pages.value > 0
    # Serving never depended on the spill landing.
    assert eng.metrics.completed.value == 3


def test_export_import_handoff_token_identical(tiny):
    pre = _engine(tiny)
    dec = _engine(tiny)
    prompt = list(range(1, 50))  # 3 full pages + tail
    blob, shipped = pre.export_kv(list(prompt))
    assert shipped == 48
    res = dec.import_kv(blob)
    assert res["tokens"] == shipped and res["installed_pages"] == 3
    dec.submit(list(prompt), max_new_tokens=4, temperature=0.0, seed=0)
    out_dec = list(dec.run().values())[0]
    m = dec.metrics
    # Reused tokens == shipped tokens, attributed to the handoff tier.
    assert m.prefix_cache_hit_tokens.value == shipped
    assert m.prefix_cache_hit_tokens_by_tier["handoff"].value == shipped
    # Token-identical to a local prefill+decode of the same request.
    pre.submit(list(prompt), max_new_tokens=4, temperature=0.0, seed=0)
    assert out_dec == list(pre.run().values())[0]
    # Re-import is a no-op install (pages already published) — and a
    # no-op must NOT feed the measured put bandwidth: clocking blob bytes
    # over a microsecond walk would inflate the kv_put_mbps the gateway's
    # cost model trusts.
    bytes0, secs0 = dec.kv_import_bytes, dec.kv_import_seconds
    res2 = dec.import_kv(blob)
    assert res2["installed_pages"] == 0 and res2["matched_pages"] == 3
    assert dec.kv_import_bytes == bytes0
    assert dec.kv_import_seconds == secs0


def test_import_rejects_torn_and_mismatched(tiny):
    from ditl_tpu.infer.continuous import BadRequestError

    pre = _engine(tiny)
    blob, _ = pre.export_kv(list(range(1, 50)))
    dec = _engine(tiny)
    with pytest.raises(KVTransferError):
        dec.import_kv(blob[: len(blob) - 5])
    bad = bytearray(blob)
    bad[len(blob) // 2] ^= 1
    with pytest.raises(KVTransferError):
        dec.import_kv(bytes(bad))
    # Geometry mismatch: a different page size must refuse cleanly.
    other = _engine(tiny, page_size=32)
    with pytest.raises(BadRequestError):
        other.import_kv(blob)
    assert dec.metrics.kv_handoff_imports.value == 0


def test_import_rejects_pool_dtype_mismatch(tiny):
    # Pool dtype is geometry too: the install scatter would silently CAST
    # a mismatched blob (f32 pages into a bf16 pool) — outputs would stop
    # being token-identical to a local prefill with no error signal.
    import dataclasses

    import jax

    from ditl_tpu.config import ModelConfig  # noqa: F401 (type context)
    from ditl_tpu.infer.continuous import BadRequestError, ContinuousEngine
    from ditl_tpu.models import llama

    cfg, params, tok = tiny
    blob, _ = _engine(tiny).export_kv(list(range(1, 50)))
    bf_cfg = dataclasses.replace(cfg, dtype="bfloat16")
    bf_params = llama.init_params(jax.random.key(0), bf_cfg)
    bf = ContinuousEngine(bf_params, bf_cfg, tok, n_slots=1, decode_chunk=4,
                          cache_mode="paged", page_size=16)
    with pytest.raises(BadRequestError, match="dtype"):
        bf.import_kv(blob)


def test_threaded_engine_call(tiny):
    from ditl_tpu.infer.continuous import ThreadedEngine

    te = ThreadedEngine(_engine(tiny))
    try:
        assert te.call(lambda: 7) == 7
        with pytest.raises(KeyError):
            te.call(lambda: {}["missing"])
        # Calls interleave with live serving without wedging the driver.
        out = te.generate_one([1, 2, 3], max_new_tokens=2, temperature=0.0,
                              seed=0)
        assert len(out) <= 2
        assert te.call(lambda: te._engine.tick_count) > 0
    finally:
        te.close()


# -- THE handoff drill (gateway, acceptance) ---------------------------------


def _fleet(tiny, tmp_path, kvtier_overrides=None, journal=True):
    from ditl_tpu.config import GatewayConfig, KVTierConfig
    from ditl_tpu.gateway import (
        Fleet, GatewayMetrics, InProcessReplica, make_gateway,
    )
    from ditl_tpu.infer.continuous import ThreadedEngine
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.infer.server import make_server
    from ditl_tpu.telemetry.journal import EventJournal

    cfg, params, tok = tiny
    shared_gen = Generator(params, cfg, tok)
    roles = ["prefill_heavy", "decode_heavy"]
    engines = [ThreadedEngine(_engine(tiny, n_slots=2, n_pages=65))
               for _ in roles]

    def factory(eng, role):
        return lambda: make_server(shared_gen, port=0, threaded_engine=eng,
                                   default_max_tokens=4, role=role,
                                   kv_handoff=True)

    fleet = Fleet([
        InProcessReplica(f"r{i}", factory(eng, role), role=role)
        for i, (eng, role) in enumerate(zip(engines, roles))
    ])
    fleet.start_all(wait_healthy_s=30.0)
    metrics = GatewayMetrics()
    jpath = os.path.join(str(tmp_path), "events-kv.jsonl")
    jr = EventJournal(jpath, source="gateway") if journal else None
    kt = KVTierConfig(handoff=True, handoff_min_prompt_tokens=8,
                      **(kvtier_overrides or {}))
    server = make_gateway(
        fleet, config=GatewayConfig(router="least_outstanding"),
        metrics=metrics, port=0, kvtier=kt, journal=jr,
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    return fleet, engines, metrics, server, port, jpath, jr


def _post(port, prompt, max_tokens=4):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"prompt": prompt,
                         "max_tokens": max_tokens}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _teardown(fleet, engines, server, jr):
    server.shutdown()
    server.server_close()
    fleet.stop_all(drain=True, timeout=10.0)
    for eng in engines:
        eng.close()
    if jr is not None:
        jr.close()


def _journal_rows(jpath):
    rows = []
    with open(jpath) as f:
        for line in f:
            rows.append(json.loads(line))
    return rows


def test_handoff_drill_reused_equals_shipped(tiny, tmp_path):
    fleet, engines, gm, server, port, jpath, jr = _fleet(tiny, tmp_path)
    try:
        # LONG interactive prompt: steering keeps it off prefill_heavy, so
        # the decode replica serves it — and the cost model ships its
        # prefill over. 16 whitespace words >= the 8-token floor; ~80 byte
        # tokens = 5 full pages at page 16.
        long_prompt = " ".join(f"word{i:03d}" for i in range(16))
        out = _post(port, long_prompt)
        assert out["usage"]["completion_tokens"] >= 1
        dec = engines[1]._engine
        shipped = int(dec.metrics.kv_handoff_tokens.value)
        assert shipped > 0
        # Reused tokens == shipped tokens, pinned from the PR 8 counters:
        # the decode replica decoded WITHOUT locally prefilling the
        # shipped pages.
        assert dec.metrics.prefix_cache_hit_tokens.value == shipped
        assert dec.metrics.prefix_cache_hit_tokens_by_tier[
            "handoff"].value == shipped
        # The prefill replica did the prefill work (pages published).
        pre = engines[0]._engine
        assert pre.prefill_tokens_total >= shipped
        # SHORT prompt: the cost model must decline (re-prefill wins).
        out = _post(port, "hi there")
        assert out["usage"]["completion_tokens"] >= 1
        assert int(gm.handoff_shipped.value) == 1
        assert int(gm.handoff_declined.value) == 1
        assert int(gm.handoff_fallback.value) == 0
        if jr is not None:
            jr.close()
        rows = _journal_rows(jpath)
        decisions = [r for r in rows if r["event"] == "kv.handoff.decision"]
        # Both cost-model branches taken, with both estimates journaled
        # per request.
        assert {d["decision"] for d in decisions} == {"ship", "decline"}
        for d in decisions:
            assert d["est_transfer_s"] > 0 and d["est_prefill_s"] > 0
        shipped_rows = [r for r in rows if r["event"] == "kv.handoff.shipped"]
        assert len(shipped_rows) == 1 and shipped_rows[0]["bytes"] > 0
    finally:
        _teardown(fleet, engines, server, None)


def test_chaos_kill_mid_handoff_falls_back(tiny, tmp_path):
    fleet, engines, gm, server, port, jpath, jr = _fleet(tiny, tmp_path)
    try:
        long_a = " ".join(f"worda{i:03d}" for i in range(16))
        long_b = " ".join(f"wordb{i:03d}" for i in range(16))
        # Leg 1: injected failure on the handoff orchestration.
        chaos.arm(FaultPlane(rules="kv.handoff:error@max=1"))
        out = _post(port, long_a)
        assert out["usage"]["completion_tokens"] >= 1
        chaos.disarm()
        assert int(gm.handoff_fallback.value) == 1
        # Leg 2: a REAL kill — the prefill replica's server dies (sockets
        # severed = in-process kill -9) UNDERNEATH its handle, so the
        # gateway still believes it's live: the prefill hop fails
        # mid-handoff and the request must still complete via plain relay
        # + local re-prefill.
        fleet.handle("r0")._server.kill()
        out = _post(port, long_b)
        assert out["usage"]["completion_tokens"] >= 1
        assert int(gm.handoff_fallback.value) == 2
        # Zero shipped pages reached the decode replica: it re-prefilled.
        dec = engines[1]._engine
        assert dec.metrics.prefix_cache_hit_tokens_by_tier[
            "handoff"].value == 0
        assert dec.metrics.prefix_cache_miss_tokens.value > 0
        if jr is not None:
            jr.close()
        rows = _journal_rows(jpath)
        assert sum(r["event"] == "kv.handoff.fallback" for r in rows) == 2
    finally:
        _teardown(fleet, engines, server, None)
