"""Multi-host checkpoint drill worker — run by tests/test_elastic.py.

Every checkpoint test before this PR ran at ``process_count == 1``, where
Orbax's multi-host coordination (each process writes its addressable shards;
the primary commits) never executes. This script is launched as N real OS
processes via tests/cluster_harness.py and exercises the cross-process
checkpoint contract in both directions:

  save <ckpt_dir>     mesh fsdp=N: params/optimizer state sharded ACROSS
                      PROCESSES; two real train/step.py gradient steps (the
                      DP/FSDP collectives cross the process boundary), then
                      an Orbax save in which every process contributes its
                      shards, committed and fsynced before exit.
  restore <ckpt_dir>  a FRESH pod (new coordinator port, new processes)
                      rebuilds only the abstract param tree with shardings
                      and calls CheckpointManager.restore_latest_params —
                      the serving-restore path (checkpoint.py) in its first
                      cross-process exercise.
  rejoin <port2>      in-process re-init contract (distributed.py), both
                      polarities: BEFORE any computation a process may
                      rejoin a new generation on a bumped port (client
                      swap only); AFTER a computation jax cannot rewire
                      the backend's collective channels, and the re-init
                      must refuse with the actionable relaunch error, not
                      jax's generic one.

Markers printed on stdout (parsed by the test):
  RENDEZVOUS-OK   distributed runtime up at the expected process count
  SHARDED ...     some param's addressable shard is a PROPER subset of its
                  global shape — proof this process holds a real shard
  FINGERPRINT ... pod-global param fingerprint (collective sum of squares;
                  identical on every process, comparable across pods)
  SAVED / RESTORED-PARAMS   the Orbax operation completed
  SHUTDOWN-OK     clean collective teardown

Usage: python tests/elastic_drill.py <proc_id> <nproc> <port> <mode> <dir>
"""

from __future__ import annotations

import os
import sys


def _fingerprint(params) -> float:
    """Pod-global sum of squares over every param leaf: a jit reduction over
    globally-sharded arrays, so the collective itself crosses processes and
    every process prints the identical value."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fp(p):
        leaves = jax.tree_util.tree_leaves(p)
        return sum(jnp.vdot(x.astype(jnp.float32), x.astype(jnp.float32))
                   for x in leaves)

    return float(fp(params))


def _shard_proof(proc_id: int, params) -> None:
    """Print one param whose local shard is smaller than its global shape."""
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        shard = leaf.addressable_shards[0].data.shape
        if shard != leaf.shape:
            print(
                f"SHARDED p{proc_id} {jax.tree_util.keystr(path)} "
                f"local={shard} global={leaf.shape}",
                flush=True,
            )
            return
    print(f"UNSHARDED p{proc_id} (no leaf had a proper shard)", flush=True)


def _synthetic_batch(proc_id: int, host_rows: int, seq_len: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(100 + proc_id)  # distinct data per process
    ids = rng.integers(3, vocab - 4, size=(host_rows, seq_len)).astype(np.int32)
    return {
        "input_ids": ids,
        "loss_mask": np.ones((host_rows, seq_len), np.float32),
        "labels": np.zeros((host_rows,), np.int32),
        "segment_ids": np.ones((host_rows, seq_len), np.int32),
        "positions": np.tile(
            np.arange(seq_len, dtype=np.int32), (host_rows, 1)
        ),
    }


def _rejoin_leg(proc_id: int, nproc: int, port: str, port2: str) -> int:
    import jax

    from ditl_tpu.config import RuntimeConfig
    from ditl_tpu.runtime import distributed as rt

    def cfg(p):
        return RuntimeConfig(
            distributed=True, coordinator_address=f"127.0.0.1:{p}",
            num_processes=nproc, process_id=proc_id,
        )

    # Generation 0: raw client bring-up with NO backend touch (init_runtime
    # would log device info, which initializes the backend and forecloses
    # any in-process rejoin).
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc, process_id=proc_id,
    )
    # Polarity 1: no computation has run — the client swap to the bumped
    # port must succeed and the new generation's collectives must work.
    rt.reinit_distributed(cfg(port2))
    rt.barrier("rejoined")
    assert jax.process_count() == nproc
    print(f"REJOIN-OK p{proc_id}", flush=True)
    # Polarity 2: a computation HAS run (the barrier above) — rejoining yet
    # another generation must refuse with the actionable relaunch error.
    try:
        rt.reinit_distributed(cfg(int(port2) + 1))
        print(f"REJOIN-REFUSAL-MISSED p{proc_id}", flush=True)
        return 1
    except RuntimeError as e:
        if "Relaunch the process to rejoin" not in str(e):
            print(f"REJOIN-WRONG-ERROR p{proc_id} {e}", flush=True)
            return 1
        print(f"REJOIN-REFUSED p{proc_id}", flush=True)
    return 0


def main() -> int:
    proc_id, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode, ckpt_dir = sys.argv[4], sys.argv[5]

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from ditl_tpu.config import (
        MeshConfig, ModelConfig, RuntimeConfig, TrainConfig,
    )
    from ditl_tpu.runtime import distributed as rt
    from ditl_tpu.runtime.mesh import build_mesh

    if mode == "rejoin":
        return _rejoin_leg(proc_id, nproc, port, ckpt_dir)

    rt.init_runtime(RuntimeConfig(
        distributed=True,
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=proc_id,
    ))
    assert jax.process_count() == nproc, jax.process_count()
    rt.barrier("elastic-drill-startup")
    print(f"RENDEZVOUS-OK p{proc_id} procs={jax.process_count()}", flush=True)

    from ditl_tpu.parallel.sharding import named_sharding_tree
    from ditl_tpu.train.checkpoint import CheckpointManager, DataIterState
    from ditl_tpu.train.state import create_train_state, state_logical_axes
    from ditl_tpu.train.step import _default_rules, make_train_step

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=64,
    )
    train_cfg = TrainConfig(total_steps=2, warmup_steps=1)
    # fsdp across the processes: params/optimizer genuinely sharded over the
    # process boundary (pure DP would replicate them).
    mesh = build_mesh(MeshConfig(data=1, fsdp=nproc))
    rules = _default_rules(mesh)
    state_shardings = named_sharding_tree(
        mesh, state_logical_axes(cfg, train_cfg), rules
    )

    if mode == "save":
        from ditl_tpu.data.loader import make_global_batch

        with mesh:
            init_fn = jax.jit(
                lambda r: create_train_state(r, cfg, train_cfg),
                out_shardings=state_shardings,
            )
            state = init_fn(jax.random.key(0))
        host_batch = _synthetic_batch(proc_id, 2, 32, cfg.vocab_size)
        example = make_global_batch(mesh, host_batch)
        train_step = make_train_step(cfg, train_cfg, mesh, example)
        for s in range(2):
            batch = make_global_batch(
                mesh, _synthetic_batch(proc_id * 31 + s, 2, 32, cfg.vocab_size)
            )
            state, metrics = train_step(state, batch)
        loss = float(metrics["loss"])
        assert loss == loss, "loss is NaN"
        print(f"STEP p{proc_id} {int(state.step)}", flush=True)
        _shard_proof(proc_id, state.params)
        ckpt = CheckpointManager(ckpt_dir, save_every=1)
        ckpt.save(int(state.step), state, DataIterState(0, 2, 2))
        ckpt.wait()
        ckpt.close()
        print(f"FINGERPRINT p{proc_id} {_fingerprint(state.params):.8e}",
              flush=True)
        print(f"SAVED p{proc_id}", flush=True)
    elif mode == "restore":
        # Serving path: abstract params WITH shardings, no optimizer state
        # read, each process restores only its addressable shards.
        abstract_state = jax.eval_shape(
            lambda: create_train_state(jax.random.key(0), cfg, train_cfg)
        )
        abstract_params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abstract_state.params,
            state_shardings.params,
        )
        ckpt = CheckpointManager(ckpt_dir)
        params = ckpt.restore_latest_params(abstract_params)
        ckpt.close()
        assert params is not None, f"no checkpoint found in {ckpt_dir}"
        _shard_proof(proc_id, params)
        print(f"FINGERPRINT p{proc_id} {_fingerprint(params):.8e}", flush=True)
        print(f"RESTORED-PARAMS p{proc_id}", flush=True)
    else:
        print(f"UNKNOWN-MODE {mode}", flush=True)
        return 2

    rt.shutdown_runtime()
    print(f"SHUTDOWN-OK p{proc_id}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
