"""End-to-end trainer (L5) — the TPU-native analog of the reference's
``main()`` (ref ``src/distributed_inference.py:43-84``), upgraded from a fake
per-example device op to a real sharded fine-tune:

  setup_logging -> init_runtime -> mesh -> consistency check -> data pipeline
  -> sharded state init -> compiled train loop (metrics, checkpoints, optional
  process-0 API eval) -> clean teardown.

Every host runs this identical program (SPMD); they differ only in which data
shards and array shards they hold.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import numpy as np

from ditl_tpu.chaos import arm_chaos
from ditl_tpu.client.eval_loop import run_api_eval
from ditl_tpu.client.llm import LLMClient
from ditl_tpu.config import Config
from ditl_tpu.data.dataset import load_text_dataset
from ditl_tpu.data.loader import DataPipeline
from ditl_tpu.data.tokenizer import get_tokenizer
from ditl_tpu.models import llama
from ditl_tpu.native import dataprep
from ditl_tpu.parallel.sharding import named_sharding_tree, placement
from ditl_tpu.runtime.consistency import check_cross_host_consistency
from ditl_tpu.runtime.distributed import (
    barrier,
    device_summary,
    init_runtime,
    is_coordinator,
    shutdown_runtime,
)
from ditl_tpu.runtime.elastic import emit_heartbeat
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.telemetry import (
    STEP_RING,
    Anomaly,
    AnomalyPlane,
    EventJournal,
    FlightRecorder,
    GoodputTracker,
    IncidentManager,
    MemoryWatcher,
    StartupRecorder,
    StepAnatomy,
    Tracer,
    TrainingDetector,
    lost_work_from_journal,
    read_journal,
    worker_journal_path,
)
from ditl_tpu.telemetry.anomaly import NonFiniteMetricError
from ditl_tpu.train.checkpoint import CheckpointManager, DataIterState
from ditl_tpu.train.metrics import MetricsLogger
from ditl_tpu.train.state import TrainState, create_train_state, state_logical_axes
from ditl_tpu.train.step import make_eval_step, make_multi_step, make_train_step
from ditl_tpu.utils.logging import get_logger, setup_logging
from ditl_tpu.utils.profiling import StepProfiler, compile_counter

logger = get_logger(__name__)

__all__ = ["train"]


def _params_from_hf_checkpoint(path: str, model_cfg, current_params, param_shardings):
    """Convert a local HF checkpoint and merge it over the live param tree.

    Subtrees the checkpoint cannot provide (LoRA adapters) keep their fresh
    init; everything else is validated against the model config (a silently
    wrong vocab/hidden size would otherwise train on garbage gathers) and
    device_put leaf-wise onto its existing sharding.
    """
    from ditl_tpu.models.convert import load_hf_model

    logger.info("initializing params from HF checkpoint %s", path)
    np_params, hf_cfg = load_hf_model(path)
    mismatches = [
        f"{f}: checkpoint {getattr(hf_cfg, f)} != model {getattr(model_cfg, f)}"
        for f in (
            "vocab_size", "hidden_size", "intermediate_size", "num_layers",
            "num_heads", "num_kv_heads", "head_dim", "num_experts",
            "tie_embeddings",
        )
        if getattr(hf_cfg, f) != getattr(model_cfg, f)
    ]
    if mismatches:
        raise ValueError(
            f"HF checkpoint {path} does not match the model config: "
            + "; ".join(mismatches)
        )

    def merge(hf_sub, cur_sub, shard_sub):
        if isinstance(cur_sub, dict):
            return {
                k: merge(hf_sub.get(k) if hf_sub else None, v, shard_sub[k])
                for k, v in cur_sub.items()
            }
        if hf_sub is None:  # e.g. LoRA adapters: keep fresh init
            return cur_sub
        return jax.device_put(hf_sub.astype(model_cfg.param_dtype), shard_sub)

    return merge(np_params, current_params, param_shardings)


def _windows(it, size: int):
    """Group an iterator into lists of up to ``size`` items."""
    import itertools

    while True:
        window = list(itertools.islice(it, size))
        if not window:
            return
        yield window


def _timed_iter(it, on_wait):
    """Pass-through iterator reporting the host wall spent blocked in each
    ``next()`` to ``on_wait`` — the data-wait phase of the step breakdown
    (prefetch usually makes this ~0; when it isn't, the pipeline is the
    bottleneck and this is the number that says so)."""
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            on_wait(time.perf_counter() - t0)
            return
        on_wait(time.perf_counter() - t0)
        yield item


def _run_validation(eval_step, params, val_batches, mesh) -> float:
    """Token-weighted mean NLL over the pre-materialized held-out batches
    (host numpy; shipped to the mesh per pass)."""
    from ditl_tpu.data.loader import make_global_batch

    tot_nll = tot_tok = 0.0
    for host_batch in val_batches:
        batch = make_global_batch(mesh, host_batch)
        aux = eval_step(params, batch)
        n = float(aux["n_tokens"])
        tot_nll += float(aux["loss"]) * n
        tot_tok += n
    return tot_nll / max(tot_tok, 1.0)


def _crossed(step: int, n_advanced: int, every: int) -> bool:
    """True if the last ``n_advanced`` steps ending at ``step`` crossed a
    multiple of ``every`` — cadence checks that stay correct when the loop
    advances in windows (steps_per_call > 1), where ``step % every == 0``
    would fire only when a window boundary happens to align."""
    return every > 0 and step > 0 and (step // every) > ((step - n_advanced) // every)


def train(config: Config,
          startup: StartupRecorder | None = None) -> dict[str, Any]:
    """Run the full fine-tune. Returns summary metrics (also logged).

    ``startup``: the process's start-up clock where the caller made one at
    its own entry (``launch.main``, whose ``config`` leg it already holds);
    else the start is counted from here. Seven contiguous legs up to the
    first metrics flush's sync: the first ``metrics_file`` row carries them,
    and a journaled run (``train.telemetry_dir``) writes them as
    ``startup.*`` spans."""
    t_start = time.time()
    if startup is None:
        startup = StartupRecorder(t_start)
    # Always-on goodput accounting (telemetry/goodput.py): pure host wall
    # clocks, zero device syncs. Every second of this run lands in a bucket
    # (productive step / compile / data-wait / checkpoint / eval / profiler
    # / restart lost-work) or the measured "other" remainder.
    tracker = GoodputTracker()
    tracker.start()
    init_runtime(config.runtime)
    setup_logging(config.runtime.log_level)
    journal: EventJournal | None = None
    if config.train.telemetry_dir:
        journal = EventJournal(
            worker_journal_path(
                config.train.telemetry_dir, jax.process_index()
            ),
            source=f"worker-{jax.process_index()}",
            max_bytes=config.telemetry.journal_max_bytes(),
        )
        journal.event("worker.start")
        compile_counter().journal = journal  # one jit.compile event a program
    tracer = Tracer(journal)
    startup.attach(tracer)  # startup.* spans, the closed legs backdated
    # Chaos plane (ditl_tpu/chaos/, ISSUE 5): armed pod-wide from the
    # identical config (the fingerprint covers chaos.*); per-worker
    # targeting via rule `proc=N`. Injections journal into this worker's
    # event stream so the merged pod timeline shows inject -> death ->
    # relaunch -> recovery in causal order; fire counts persist under
    # telemetry_dir so `max=N` caps survive the kills they inject.
    arm_chaos(
        config.chaos,
        journal=journal,
        process_id=jax.process_index(),
        state_dir=config.chaos.journal_dir or config.train.telemetry_dir,
    )
    mesh = build_mesh(config.mesh)
    startup.mark("runtime")
    model_cfg = config.model  # preset resolution happens in launch.build_config
    # Adapter publication (ISSUE 16): misconfiguration fails HERE, before
    # any compile — a publish cadence with nowhere to write (or no LoRA to
    # slice out) would otherwise surface as a mid-run crash at the first
    # cadence crossing.
    if config.adapter.publish_every > 0:
        if not config.adapter.publish_dir:
            raise ValueError(
                "adapter.publish_every is set but adapter.publish_dir is "
                "empty: the trainer has nowhere to commit adapter "
                "checkpoints")
        if model_cfg.lora_rank <= 0:
            raise ValueError(
                "adapter.publish_every needs model.lora_rank > 0: "
                "adapter-only publication exports the LoRA slice of the "
                "params, and a full fine-tune has none")

    tokenizer = get_tokenizer(config.data.tokenizer)
    if model_cfg.vocab_size < tokenizer.vocab_size:
        raise ValueError(
            f"model vocab {model_cfg.vocab_size} < tokenizer vocab {tokenizer.vocab_size}"
        )
    dataset = load_text_dataset(config.data)
    if (config.data.eval_fraction > 0) != (config.train.val_every > 0):
        raise ValueError(
            "data.eval_fraction and train.val_every must be set together "
            f"(got eval_fraction={config.data.eval_fraction}, "
            f"val_every={config.train.val_every}): one without the other "
            "either wastes held-out data or never validates"
        )
    val_dataset = None
    if config.data.eval_fraction > 0:
        # Deterministic seeded permutation before the split: every host
        # computes the same boundary, and label-ordered corpora (HF imdb is
        # stored label-sorted) don't produce a single-class holdout.
        n_val = max(1, int(len(dataset) * config.data.eval_fraction))
        n_train = len(dataset) - n_val
        if n_train < 1:
            raise ValueError(
                f"eval_fraction {config.data.eval_fraction} leaves no training data"
            )
        from ditl_tpu.data.dataset import TextDataset

        perm = np.random.default_rng(config.data.seed).permutation(len(dataset))
        texts = [dataset.texts[i] for i in perm]
        labels = [dataset.labels[i] for i in perm]
        val_dataset = TextDataset(texts[n_train:], labels[n_train:])
        dataset = TextDataset(texts[:n_train], labels[:n_train])
    # Consistency check runs AFTER data loading so a host that silently fell
    # back to the synthetic corpus (hub hiccup) is caught before any
    # collective, not after a divergent epoch hangs one (SURVEY.md §5).
    check_cross_host_consistency(
        config,
        extra={
            "dataset_len": len(dataset),
            "dataset_head": [dataset[i]["text"][:64] for i in range(min(3, len(dataset)))],
        },
    )
    pipeline = DataPipeline(dataset, tokenizer, config.data, mesh)
    logger.info(
        "dataset: %d examples, %d steps/epoch (host batch %d, global %d)",
        len(dataset),
        pipeline.steps_per_epoch,
        pipeline.host_batch_size,
        config.data.batch_size,
    )
    startup.mark("data", examples=len(dataset))

    # Sharded-from-birth state init: jit with out_shardings so every param is
    # created directly on its mesh shards (a 70B state never fits one chip).
    # Rule table must match the train step's (stage-sharded when pipelined).
    from ditl_tpu.train.step import _default_rules

    rules = _default_rules(mesh)
    state_shardings = named_sharding_tree(
        mesh, state_logical_axes(model_cfg, config.train), rules
    )
    rng = jax.random.key(
        config.train.seed if config.train.init_seed < 0 else config.train.init_seed)
    with mesh:
        init_fn = jax.jit(
            lambda r: create_train_state(r, model_cfg, config.train),
            out_shardings=state_shardings,
        )
        state = init_fn(rng)
    n_params = llama.num_params(state.params)
    if startup.armed:
        # A leg is host wall time; only a journaled start pays the wait that
        # puts the device's share of the draw in THIS leg (synced=1).
        jax.block_until_ready(state)
    startup.mark("state", synced=int(startup.armed), n_params=n_params)
    # Which way the fused loss is laid over this mesh (ops/fused_ce.py): fixed
    # per compiled step, so written once here, on the step's jit.compile
    # event and in the summary (None: the naive loss, GSPMD's to partition).
    loss_part = None
    if model_cfg.loss_impl == "fused":
        from ditl_tpu.ops.fused_ce import loss_partition

        loss_part = str(loss_partition(mesh, rules) or "local")
    for program in ("jit(train_step)", "jit(train_multi_step)"):
        compile_counter().notes[program] = {"loss_partition": loss_part}
    logger.info(
        "model %s: %.2fM params, loss_partition %s",
        model_cfg.name, n_params / 1e6, loss_part,
    )

    # Checkpoint manager + resume.
    ckpt: CheckpointManager | None = None
    data_iter = DataIterState()
    resumed = False
    if config.train.checkpoint_dir:
        ckpt = CheckpointManager(
            config.train.checkpoint_dir,
            max_to_keep=config.train.keep_checkpoints,
            save_every=config.train.checkpoint_every,
            # Commit/quarantine/fallback events land in this worker's
            # journal — the kill-mid-save drill asserts them in order.
            journal=journal,
        )
        if config.train.resume:
            abstract = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                jax.eval_shape(lambda: state),
                state_shardings,
            )
            restored = ckpt.restore_latest(abstract)
            if restored is not None:
                state, data_iter = restored
                resumed = True
                logger.info(
                    "restored checkpoint: resuming from step %d "
                    "(epoch %d, batch offset %d)",
                    int(state.step), data_iter.epoch, data_iter.step_in_epoch,
                )
                if journal is not None:
                    # Restart lost-work: the previous generation's journal
                    # (same per-process file, appended across generations)
                    # brackets the span between the checkpoint we resumed at
                    # and its last sign of life.
                    lost = lost_work_from_journal(
                        read_journal(journal.path),
                        data_iter.global_step, t_start,
                    )
                    tracker.add("restart_lost_work", lost)
                    journal.event(
                        "worker.resume", step=data_iter.global_step,
                        lost_work_s=round(lost, 6),
                    )

    if config.train.init_from_hf and not resumed:
        # Overwrite the random base weights with a converted HF checkpoint
        # (skipped on resume — the Orbax checkpoint supersedes it). Leaf-wise
        # device_put onto each param's existing sharding; the full model is
        # never resident on one chip.
        state = state.replace(
            params=_params_from_hf_checkpoint(
                config.train.init_from_hf, model_cfg, state.params,
                state_shardings.params,
            )
        )
    # The checkpoint manager and a resume are one leg, and the goodput
    # report's checkpoint_restore bucket where a resume was asked for.
    restore_s = startup.mark("restore", resumed=resumed,
                             step=data_iter.global_step)
    if ckpt is not None and config.train.resume:
        tracker.add("checkpoint_restore", restore_s)
    else:
        restore_s = 0.0

    val_batches = None
    if val_dataset is not None and config.train.val_every > 0:
        import dataclasses as _dc
        import itertools as _it

        val_pipeline = DataPipeline(
            val_dataset,
            tokenizer,
            _dc.replace(config.data, shuffle=False),
            mesh,
        )
        # Materialize the validation window ONCE as HOST batches: shuffle is
        # off, so they are identical every run — re-tokenizing/packing the
        # holdout at each val_every would stall training — but keeping them
        # in host RAM (not HBM) means validation costs no standing device
        # memory; each pass device_puts them transiently. This is also the
        # only accurate emptiness check for the packed path (document counts
        # don't predict packed batch counts).
        val_batches = list(
            _it.islice(val_pipeline._host_batches(0), config.train.val_batches)
        )
        if not val_batches:
            raise ValueError(
                f"eval_fraction {config.data.eval_fraction} holds out too few "
                f"tokens for even one validation batch (batch {config.data.batch_size}"
                f" x seq {config.data.seq_len}); increase it or shrink the batch"
            )

    example = next(iter(pipeline.epoch(0)))
    train_step = make_train_step(model_cfg, config.train, mesh, example)
    eval_step = None
    spc = max(1, config.train.steps_per_call)
    train_multi = (
        make_multi_step(model_cfg, config.train, mesh, example, spc)
        if spc > 1
        else None
    )

    # Step-time anatomy (telemetry/perf.py, ISSUE 7): the per-step wall
    # decomposition the goodput report is too coarse for. Attached to the
    # MetricsLogger AFTER the compile window (goodput attributes that whole
    # window to compile; anatomy describes warm steps only) and conserved
    # against the independently measured step-path wall to 5% in tier-1.
    anatomy = StepAnatomy()
    # HBM accounting (telemetry/memwatch.py): per-window allocator samples
    # (high-watermark gauges) + a journaled live-buffer top-k dump when an
    # OOM-class failure unwinds the loop. No-op on statless backends (CPU).
    memwatch = MemoryWatcher(
        journal=journal, topk=config.telemetry.memory_topk,
    )
    # Flight recorder + anomaly plane (ISSUE 10): the per-step ring and the
    # non-finite/spike/explosion detectors ride the EXISTING log_every host
    # flush (train/metrics.py on_host_metrics) — always on, zero device
    # syncs beyond the flush the metrics path already pays (tier-1-pinned).
    # Incident bundles are assembled only when telemetry.incident_dir is
    # set; a fatal detection (non-finite loss/grad) dumps its bundle and
    # THEN crashes the run, so the evidence precedes the stack trace.
    flight = FlightRecorder(config.telemetry.flight_ring_size)
    incidents: IncidentManager | None = None
    if config.telemetry.incident_dir:
        import os as _os

        incidents = IncidentManager(
            # Per-worker subdirectory: SPMD replicates the loss, so a NaN
            # fires the fatal detector in EVERY worker at once — each
            # writes (and GCs, and sweeps tmp dirs) in its own directory
            # rather than racing peers in a shared one.
            _os.path.join(config.telemetry.incident_dir,
                          f"worker-{jax.process_index()}"),
            flight=flight,
            metrics_render=memwatch.registry.render,
            journal_dir=config.train.telemetry_dir,
            registry=memwatch.registry,
            config_snapshot=config.to_dict(),
            memwatch_dump=memwatch.report,
            source=f"worker-{jax.process_index()}",
            **config.telemetry.incident_kwargs(),
        )
    anomaly_plane = AnomalyPlane(incidents=incidents, journal=journal)
    # Continuous sampling profiler (ISSUE 18): armed by telemetry.prof_hz,
    # off by default. Phase-tagged across the step loop so StepAnatomy's
    # host_dispatch bucket gains stack attribution in the summary, and
    # incident bundles embed the collapsed profile (profile.txt).
    sampler = None
    if config.telemetry.prof_hz > 0:
        from ditl_tpu.telemetry.prof import SamplingProfiler

        sampler = SamplingProfiler(
            hz=config.telemetry.prof_hz,
            max_stacks=config.telemetry.prof_max_stacks,
            registry=memwatch.registry,
        )
        sampler.arm_phases()  # this (the step-loop) thread
        sampler.start()
    train_detector = TrainingDetector(
        **config.telemetry.training_detector_kwargs()
    )
    _fatal: list[Anomaly] = []
    _in_teardown = [False]

    def _fatal_error() -> NonFiniteMetricError:
        return NonFiniteMetricError(
            f"non-finite training metric at step "
            f"{_fatal[0].detail.get('step', '?')}: "
            f"{_fatal[0].kind} {_fatal[0].detail}"
        )

    def _on_host_metrics(step: int, host: dict, dt: float) -> None:
        flight.ring(STEP_RING).record(
            step=step,
            loss=host.get("loss"),
            grad_norm=host.get("grad_norm"),
            n_tokens=host.get("n_tokens"),
            step_time_s=round(dt, 6),
        )
        for anomaly in train_detector.observe_step(
            step, host.get("loss"), host.get("grad_norm")
        ):
            anomaly_plane.trigger(anomaly)
            if anomaly.severity == "fatal":
                _fatal.append(anomaly)
        if _fatal and not _in_teardown[0]:
            # Bundle already assembled above; now crash the run the way a
            # real divergence would have a few steps later — loudly, with
            # the black box on disk. NOT raised during teardown: the
            # catch-up flush inside metrics.close() runs in the finally
            # block, where raising would skip the rest of teardown (and
            # the end-of-training barrier) and mask any original
            # exception — a tail-window detection raises AFTER teardown
            # instead (below).
            raise _fatal_error()

    metrics = MetricsLogger(
        log_every=config.train.log_every,
        metrics_file=config.train.metrics_file,
        on_host_metrics=_on_host_metrics,
        startup=startup,
    )
    profiler = StepProfiler(
        config.train.profile_dir,
        config.train.profile_start_step,
        config.train.profile_num_steps,
        # ISSUE 6 satellite: a journaled run records the xprof capture
        # window as a `profiler.capture` span on the training-leg timeline
        # (not only as a goodput bucket).
        tracer=tracer,
    )
    client = LLMClient(config.api)
    total_steps = config.train.total_steps
    global_step = data_iter.global_step
    def beat(step: int) -> None:
        """Publish liveness for the pod controller (runtime/elastic.py)."""
        if config.train.heartbeat_dir:
            emit_heartbeat(config.train.heartbeat_dir, jax.process_index(), step)

    # First heartbeat BEFORE the first step: first-step compile can dominate
    # wall time, and the pod controller must read "alive, still compiling"
    # rather than "never came up".
    beat(global_step)
    step_metrics = None
    last_val_loss = None
    last_saved = None
    epoch = data_iter.epoch

    # Everything before the loop is startup, on the recorder's clock (less
    # the leg already attributed to a finer bucket, the checkpoint restore,
    # and what ran before this function: the tracker's own total starts here).
    startup.mark("loop_prep")
    tracker.add("startup",
                startup.total() - restore_s - (t_start - startup.entry_wall))
    data_wait_acc = [0.0]  # host wall blocked in the data iterator, per window

    def _note_wait(dt: float) -> None:
        data_wait_acc[0] += dt
        tracker.add("data_wait", dt)

    first_window = True
    try:
        for epoch in range(data_iter.epoch, config.data.num_epochs):
            # Resume skips already-consumed batches at the sampler level.
            start = data_iter.step_in_epoch if epoch == data_iter.epoch else 0
            batch_iter = _timed_iter(
                iter(pipeline.epoch(epoch, start_step=start)), _note_wait
            )
            step_in_epoch = start
            for window in _windows(batch_iter, spc):
                if global_step >= total_steps:
                    break
                window = window[: total_steps - global_step]
                t_window0 = time.perf_counter()
                metrics.start_step()
                # Profiler work (start_trace, and maybe_stop's wait for the
                # traced steps + trace write) happens INSIDE the window
                # interval — timed explicitly and subtracted from the
                # window wall below, or it would be double-counted into
                # compile/productive_step and break conservation.
                profiler.maybe_start(global_step)
                prof_s = time.perf_counter() - t_window0
                if sampler is not None:
                    # Tag the dispatch window: samples landing here
                    # attribute StepAnatomy's host_dispatch bucket to
                    # real frames in the summary (one attribute write).
                    sampler.set_phase("host_dispatch")
                with profiler.annotate(global_step):
                    if train_multi is not None and len(window) == spc:
                        # One device program runs the whole window: zero host
                        # dispatch between steps (train/step.make_multi_step).
                        import jax.numpy as jnp

                        stacked = jax.tree.map(
                            lambda *xs: jnp.stack(xs, axis=0), *window
                        )
                        state, ms = train_multi(state, stacked)
                        step_metrics = {k: v[-1] for k, v in ms.items()}
                        window_metrics = dict(
                            step_metrics, n_tokens=ms["n_tokens"].sum()
                        )
                    else:  # window shorter than spc (epoch tail): single steps
                        window_tokens = None
                        for batch in window:
                            state, step_metrics = train_step(state, batch)
                            window_tokens = (
                                step_metrics["n_tokens"]
                                if window_tokens is None
                                else window_tokens + step_metrics["n_tokens"]
                            )
                        window_metrics = dict(step_metrics, n_tokens=window_tokens)
                t_prof = time.perf_counter()
                profiler.maybe_stop(
                    global_step + len(window) - 1, window_metrics
                )
                prof_s += time.perf_counter() - t_prof
                tracker.add("profiler", prof_s)
                global_step += len(window)
                step_in_epoch += len(window)
                window_wait, data_wait_acc[0] = data_wait_acc[0], 0.0
                metrics.end_step(
                    global_step - 1, window_metrics, n_steps=len(window),
                    data_wait_s=window_wait,
                    # Profiler work inside the window interval has its own
                    # goodput bucket AND is subtracted from the anatomy
                    # wall below — exclude it from the anatomy's dispatch
                    # feed too, or a capture window would break the 5%
                    # conservation invariant.
                    excluded_s=prof_s,
                )
                if sampler is not None:
                    sampler.set_phase(None)
                # Window wall (dispatch + any flush sync inside end_step;
                # data wait happened before the window body, profiler work
                # is subtracted — both have their own buckets): the FIRST
                # compiled window is compile-dominated, so it is attributed
                # to the compile badput bucket whole — the same convention
                # summary() uses when it drops the warm-up step from p50.
                dt_window = time.perf_counter() - t_window0 - prof_s
                if first_window:
                    tracker.add("compile", dt_window)
                    first_window = False
                    # Anatomy starts AFTER the compile window: from here on
                    # the MetricsLogger feeds host_dispatch / data_wait /
                    # device_compute and the trainer adds the matching wall.
                    metrics.anatomy = anatomy
                else:
                    tracker.add_step(dt_window, len(window))
                    anatomy.add_wall(window_wait + dt_window, len(window))
                if config.telemetry.memory_sample_every and _crossed(
                    global_step, len(window),
                    config.telemetry.memory_sample_every,
                ):
                    memwatch.sample()
                if journal is not None and _crossed(
                    global_step, len(window), config.train.log_every
                ):
                    journal.event("train.progress", step=global_step)
                beat(global_step)
                position = DataIterState(epoch, step_in_epoch, global_step)
                if ckpt is not None and ckpt.should_save(global_step, len(window)):
                    t_ck0 = time.perf_counter()
                    with tracker.span("checkpoint_save"):
                        ckpt.save(global_step, state, position)
                    dt_ck = time.perf_counter() - t_ck0
                    # The blocking portion of the async save interleaves the
                    # step stream — the anatomy's checkpoint_overlap bucket
                    # (the async remainder overlaps device compute for free).
                    anatomy.add("checkpoint_overlap", dt_ck)
                    anatomy.add_wall(dt_ck)
                    if journal is not None:
                        journal.event("checkpoint.save", step=global_step)
                    last_saved = global_step
                if is_coordinator() and _crossed(
                    global_step, len(window), config.adapter.publish_every
                ):
                    # Live train->serve publication (ISSUE 16): commit the
                    # LoRA-only slice as a manifest-verified adapter
                    # checkpoint (npz + crc manifest written LAST + atomic
                    # LATEST flip) — the unit gateway/publish.py verifies
                    # and walks onto a serving fleet. LoRA leaves are tiny
                    # and replicated, so only the coordinator writes; the
                    # wall rides the checkpoint_save goodput bucket.
                    from ditl_tpu.train.adapter_export import export_adapter

                    with tracker.span("checkpoint_save"):
                        vdir = export_adapter(
                            config.adapter.publish_dir,
                            config.adapter.publish_name,
                            global_step, state.params, model_cfg,
                        )
                    if journal is not None:
                        journal.event("adapter.export", step=global_step,
                                      directory=vdir)
                    logger.info("published adapter checkpoint %s", vdir)
                if val_batches is not None and _crossed(
                    global_step, len(window), config.train.val_every
                ):
                    if eval_step is None:
                        eval_step = make_eval_step(model_cfg, mesh)
                    with tracker.span("eval"):
                        last_val_loss = _run_validation(
                            eval_step, state.params, val_batches, mesh
                        )
                    if is_coordinator():
                        logger.info(
                            "step %d: val_loss=%.4f", global_step, last_val_loss
                        )
                if _crossed(global_step, len(window), config.train.eval_every):
                    idx = np.arange(min(config.train.eval_samples, len(dataset)))
                    with tracker.span("eval"):
                        run_api_eval(
                            client,
                            [dataset[int(i)]["text"] for i in idx],
                            [dataset[int(i)]["label"] for i in idx],
                            max_samples=config.train.eval_samples,
                        )
                if _crossed(
                    global_step, len(window), config.train.val_every
                ) or _crossed(global_step, len(window), config.train.eval_every):
                    # Validation / remote-API eval can dwarf a step window;
                    # re-arm the stall watchdog so a long (healthy) eval
                    # isn't read as a wedged worker.
                    beat(global_step)
                if (
                    config.train.fault_kill_step > 0
                    and not resumed
                    and global_step >= config.train.fault_kill_step
                    and config.train.fault_kill_process
                    in (-1, jax.process_index())
                ):
                    # SIGKILL drill (host-crash simulation): bypasses every
                    # Python-level handler, so only a process-level
                    # supervisor (launch --supervise) can bring us back.
                    import os as _os
                    import signal as _signal

                    logger.error(
                        "fault_kill_step: SIGKILLing self at step %d",
                        global_step,
                    )
                    if journal is not None:
                        # Line-buffered: the event is on disk before the
                        # uncatchable kill — the timeline's first entry of
                        # the death sequence.
                        journal.event("worker.sigkill_self", step=global_step)
                    _os.kill(_os.getpid(), _signal.SIGKILL)
                if (
                    config.train.fault_inject_step > 0
                    and not resumed
                    and global_step >= config.train.fault_inject_step
                ):
                    # Recovery drill (after the save check above, so the
                    # supervisor has a checkpoint to resume from): only on a
                    # first run — a resumed run must complete.
                    raise RuntimeError(
                        f"injected fault at step {global_step} "
                        "(train.fault_inject_step)"
                    )
            if global_step >= total_steps:
                break
        # The catch-up flush after the loop blocks on the last window's
        # device work — step-path wall like any in-loop flush, so the
        # anatomy counts the interval (its sync feeds device_compute via
        # the logger hook) and conservation holds.
        t_flush0 = time.perf_counter()
        metrics.flush()
        anatomy.add_wall(time.perf_counter() - t_flush0)
        if ckpt is not None and last_saved != global_step:
            with tracker.span("checkpoint_save"):
                ckpt.save(global_step, state, DataIterState(epoch, 0, global_step))
                ckpt.wait()
            if journal is not None:
                journal.event("checkpoint.save", step=global_step)
    except Exception as e:
        # OOM post-mortem (ISSUE 7): journal the live-buffer top-k dump
        # BEFORE the finally teardown releases the step's working set, so
        # the record shows what was actually holding HBM. Non-OOM failures
        # pass through untouched.
        from ditl_tpu.telemetry.memwatch import is_oom_error

        if is_oom_error(e):
            import contextlib as _ctx

            with _ctx.suppress(Exception):
                memwatch.sample()
                memwatch.oom_dump(e)
            # OOM is an anomaly-plane trigger source (ISSUE 10): the bundle
            # freezes the step ring + the memwatch top-k alongside the
            # journaled oom_dump, before the teardown releases buffers.
            anomaly_plane.trigger(Anomaly(
                "train.oom", severity="fatal",
                detail={"step": global_step,
                        "error": f"{type(e).__name__}: {str(e)[:500]}"},
            ))
        raise
    finally:
        _in_teardown[0] = True  # tail-window flushes detect but never raise
        if sampler is not None:
            sampler.stop()
        metrics.close()
        with tracker.span("profiler"):
            profiler.close(step_metrics)
        if ckpt is not None:
            ckpt.close()
        if journal is not None:
            compile_counter().journal = None
            journal.event("worker.exit", step=global_step)
            journal.close()
        barrier("end-of-training")

    # A fatal detection surfaced only by the teardown's catch-up flush
    # (NaN in the final, un-flushed window): teardown completed cleanly
    # above — crash NOW, with the bundle already on disk.
    if _fatal:
        raise _fatal_error()

    summary = metrics.summary()
    summary["final_loss"] = (
        float(jax.device_get(step_metrics["loss"]))
        if step_metrics is not None
        else float("nan")
    )
    summary["steps"] = global_step
    if last_val_loss is not None:
        summary["val_loss"] = last_val_loss
    summary["params_m"] = n_params / 1e6
    summary["wall_s"] = time.time() - t_start
    # What this run actually ran on, as jax reports it, and whether the
    # host data path was the C++ library or its Python fallback — a caller
    # reads both from the summary instead of trusting a log line.
    summary["device"] = device_summary()
    summary["native_dataprep"] = dataprep.loaded()
    # Parameters + optimizer state by device, from the arrays' shardings.
    summary["state_placement"] = placement(state)
    summary["loss_partition"] = loss_part
    # Goodput report: where the wall clock went, conservation-checked (the
    # tier-1 test asserts buckets + other sum to total within 1%).
    summary["goodput"] = tracker.report()
    # Step-time anatomy (ISSUE 7): the warm-step wall decomposed into
    # data-wait / host-dispatch / device-compute / checkpoint-overlap,
    # conservation-checked against the measured step-path wall to 5%.
    summary["step_anatomy"] = anatomy.report()
    # Stack attribution (ISSUE 18): when the sampling profiler was armed,
    # the anatomy's host_dispatch bucket names its hot frames — "dispatch
    # is slow" becomes "dispatch is slow IN THIS FUNCTION".
    if sampler is not None:
        frames = sampler.phase_top("host_dispatch", 5)
        if frames:
            summary["step_anatomy"]["host_dispatch_frames"] = frames
        summary["profile"] = {
            "samples": sampler.samples,
            "distinct_stacks": len(sampler.snapshot()),
            "evicted": sampler.evicted,
            "hz": sampler.hz,
        }
    # Anomaly-plane accounting (ISSUE 10): what fired and how many bundles
    # were assembled — a completed-but-noisy run is visible in its summary.
    if anomaly_plane.detected:
        summary["anomalies"] = dict(sorted(anomaly_plane.detected.items()))
    if incidents is not None:
        summary["incidents"] = incidents.created
    mem = memwatch.report()
    if mem:
        summary["memory"] = mem
    if is_coordinator():
        logger.info("training done: %s", summary)
        logger.info("goodput report: %s", summary["goodput"])
        logger.info("step anatomy: %s", summary["step_anatomy"])
    shutdown_runtime()
    return summary
