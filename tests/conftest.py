"""Test harness: simulate an 8-device pod on CPU.

This is the repaired version of the reference's broken distributed fixture —
its ``setUpClass`` ran a real ``init_process_group(world_size=2)`` in a single
process and deadlocked at the barrier (ref
``tests/test_distributed_finetuning.py:8-13``, SURVEY.md §3.5). Here
multi-device behavior is tested honestly: 8 virtual CPU devices via XLA's
host-platform device-count override, configured *before JAX's backend
initializes* (hence env mutation at conftest import time).
"""

import os

# Must happen before JAX's backends initialize (first jax.devices() call).
# Env vars alone are not enough when a pytest plugin imported jax before this
# file loaded — jax snapshots env into its config at import — so set the
# config directly too.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# NOTE: jax_compilation_cache_dir was tried here to cut suite wall time and
# reverted: XLA:CPU intermittently aborts (SIGABRT) when
# deserializing cached executables under the 8-device host platform. The
# fast tier is provided by `-m "not slow"` (pytest.ini) instead.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 simulated devices, got {len(devices)}"
    return devices


@pytest.fixture(scope="session")
def tiny_model_cfg():
    from ditl_tpu.config import ModelConfig

    return ModelConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
    )


@pytest.fixture()
def example_batch():
    rng = np.random.default_rng(0)
    b, s = 8, 32
    return {
        "input_ids": rng.integers(3, 500, size=(b, s)).astype(np.int32),
        "loss_mask": np.ones((b, s), np.float32),
        "labels": np.zeros((b,), np.int32),
        "segment_ids": np.ones((b, s), np.int32),
        "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
    }


@pytest.fixture(scope="session")
def one_chip():
    """One device of a DESCRIBED v5e, for the ``test_tpu_compile_*`` files
    (tests/tpu_compile.py). The TPU's library is loaded here, by the first
    test that asks, and never while a module is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engines():
    """An engine a ``(model, options)`` a module (``tests/family.py``): the
    cases that ask share what it builds, and each leaves it at rest."""
    from tests import family

    built = family.Engines()
    yield built
    built.close()


@pytest.fixture
def tpu_branch(monkeypatch):
    """The code asks ``jax.default_backend()``, which is the CPU here: take
    the branch the chip takes."""
    from ditl_tpu.models import moe as moe_mod
    from ditl_tpu.ops import backend, kv_flush, paged_attention, retention, ssd

    monkeypatch.setattr(moe_mod, "_use_gmm", lambda rows, mesh: rows % 128 == 0)
    # every module that bound the name at its import, whichever came first
    for module in (moe_mod, backend, kv_flush, paged_attention, retention, ssd):
        monkeypatch.setattr(module, "interpret_default", lambda: False)


# ---------------------------------------------------------------------------
# Test tiers: the default run (`pytest -q`) excludes tests marked `slow`
# (pytest.ini addopts): some 1,630 cases that sum to about 5,500 seconds,
# which the driver runs on six xdist workers, a file a worker, under a limit
# of 1,470 s (`/root/TESTS_LAST_RUN.json` has its command; ROADMAP.md Design
# 1(b) has the cost by file). No case may be moved to the list below to buy
# time: the driver's floor is the count of passes. What keeps the tier inside
# its limit is `tests/family.py`: a family's weights drawn once a worker, an
# engine built once a (model, options) a module (the `engines` fixture above).
# `pytest -m ""` runs everything; the slow set was measured (>= 3s a test,
# XLA CPU compiles dominating) when the tier was minutes long, and the driver
# never runs it (ROADMAP.md Design 3).
# ---------------------------------------------------------------------------

_SLOW_TESTS = {
    # r4 additions: pipelined ticks, optimistic admission/preemption, pod
    # fan-out, wide-head — integration-heavy (multiple engine compiles per
    # test). One fast smoke per feature stays in the default tier
    # (pipelined streaming, padded-vocab guided).
    "tests/test_preemption.py::test_optimistic_strictly_more_concurrent_at_equal_pool",
    "tests/test_preemption.py::test_preemption_exact_resume_greedy",
    "tests/test_preemption.py::test_preemption_exact_resume_sampled_logprobs",
    "tests/test_preemption.py::test_preemption_streaming_and_pipelined",
    "tests/test_preemption.py::test_optimistic_with_guided_early_finish",
    "tests/test_preemption.py::test_cancel_of_preempted_request_that_finished_while_queued",
    "tests/test_pipeline_ticks.py::test_pipelined_cancel_mid_flight",
    "tests/test_podserve.py::test_pod_continuous_generate_many_and_guided_rejection",
    "tests/test_podserve.py::test_pod_continuous_generate_many_overflow_abandons_siblings",
    "tests/test_padded_vocab.py::test_wide_head_logprobs_and_sampling_decode_safely",
    "tests/test_train.py::test_train_step_attention_bias",
    "tests/test_convert.py::test_qwen2_logits_parity[False]",
    "tests/test_logprobs.py::test_server_logprobs_via_continuous_engine",
    "tests/test_paged.py::test_paged_attention_matches_xla_reference[1]",
    "tests/test_flash_attention.py::test_forward_matches_xla[blocks1-False]",
    "tests/test_convert.py::test_mixtral_logits_parity",
    "tests/test_ring_attention.py::test_segment_ids_packing",
    "tests/test_flash_attention.py::test_forward_matches_xla[blocks1-True]",
    "tests/test_spec_continuous.py::test_spec_sampled_ticks_reproducible_and_mixed_greedy_exact",
    "tests/test_spec_continuous.py::test_spec_contiguous_matches_plain_greedy",
    "tests/test_paged.py::test_paged_attention_multi_query_matches_reference",
    "tests/test_logprobs.py::test_continuous_engine_logprobs_match_lockstep",
    "tests/test_convert.py::test_llama_logits_parity[True]",
    "tests/test_spec_continuous.py::test_spec_threshold_self_calibrates",
    "tests/test_flash_attention.py::test_grads_match_xla[True]",
    "tests/test_spec_continuous.py::test_spec_acceptance_accounted_per_request",
    "tests/test_spec_continuous.py::test_spec_streaming_chunks_concatenate_to_plain",
    "tests/test_moe_infer.py::test_moe_decode_expert_sharded_matches_single_device",
    "tests/test_podserve.py::test_pod_paged_allocator_divergence_stops_pod",
    "tests/test_continuous.py::test_short_request_admitted_during_long_prefill",
    "tests/test_ulysses.py::test_matches_full_attention[False]",
    "tests/test_stop_sequences.py::test_streaming_stop_at_full_budget_reports_stop",
    "tests/test_flash_attention.py::test_forward_matches_xla[blocks0-False]",
    "tests/test_ring_attention.py::test_matches_full_attention[False]",
    "tests/test_paged.py::test_paged_int8_kernel_matches_reference",
    "tests/test_continuous.py::test_queue_depth_cap_raises",
    "tests/test_continuous.py::test_server_returns_429_when_queue_full",
    "tests/test_podserve.py::test_pod_concurrent_requests",
    "tests/test_podserve.py::test_pod_continuous_close_fails_waiters",
    "tests/test_podserve.py::test_pod_continuous_bad_request_isolated",
    "tests/test_spec_continuous.py::test_spec_sample_tokens_matches_target_distribution",
    "tests/test_moe_infer.py::test_spec_moe_matches_plain",
    "tests/test_checkpoint.py::test_checkpoint_cadence_with_step_windows",
    "tests/test_checkpoint.py::test_trainer_resume_continues_from_checkpoint",
    "tests/test_continuous.py::test_chunked_prefill_exact_outputs",
    "tests/test_continuous.py::test_chunked_prefill_interleaves_with_decode",
    "tests/test_continuous.py::test_chunked_prefill_sampled_seed_reproducible",
    "tests/test_continuous.py::test_chunked_prefill_with_prefix_cache",
    "tests/test_continuous.py::test_matches_lockstep_generator_greedy",
    "tests/test_continuous.py::test_max_cache_len_caps_allocation",
    "tests/test_continuous.py::test_mid_flight_admission",
    "tests/test_continuous.py::test_per_request_seed_reproducible_across_batch_mixes",
    "tests/test_continuous.py::test_prefix_cache_exact_outputs",
    "tests/test_continuous.py::test_prefix_cache_longest_match_wins",
    "tests/test_continuous.py::test_prefix_cache_mixed_with_uncached",
    "tests/test_continuous.py::test_prefix_cache_whole_prompt",
    "tests/test_continuous.py::test_server_continuous_engine_concurrent",
    "tests/test_continuous.py::test_server_sse_streaming",
    "tests/test_continuous.py::test_server_sse_streaming_lockstep_fallback",
    "tests/test_continuous.py::test_slot_reuse_more_requests_than_slots",
    "tests/test_continuous.py::test_stream_one_yields_incremental_chunks",
    "tests/test_continuous.py::test_continuous_engine_on_mesh_matches_single_device",
    "tests/test_continuous.py::test_varied_max_new_and_temperature",
    "tests/test_convert.py::test_export_cli_from_orbax_checkpoint",
    "tests/test_convert.py::test_export_roundtrip",
    "tests/test_convert.py::test_llama_logits_parity[False]",
    "tests/test_convert.py::test_merge_lora_preserves_function",
    "tests/test_convert.py::test_trainer_init_from_hf",
    "tests/test_convert.py::test_trainer_init_from_hf_with_lora",
    "tests/test_flash_attention.py::test_bf16_forward_close",
    "tests/test_flash_attention.py::test_forward_matches_xla[blocks0-True]",
    "tests/test_flash_attention.py::test_gqa_groups",
    "tests/test_flash_attention.py::test_grads_match_xla[False]",
    "tests/test_fused_ce.py::test_fused_loss_matches_naive_loss_and_grads[False]",
    "tests/test_fused_ce.py::test_fused_loss_matches_naive_loss_and_grads[True]",
    "tests/test_fused_ce.py::test_fused_loss_trains_end_to_end",
    "tests/test_infer.py::test_cached_prefill_matches_uncached_forward",
    "tests/test_infer.py::test_generate_deterministic_and_batch_independent",
    "tests/test_infer.py::test_generate_on_mesh_matches_single_device",
    "tests/test_infer.py::test_generate_text_roundtrip",
    "tests/test_infer.py::test_openai_server_roundtrip_with_framework_client",
    "tests/test_infer.py::test_server_completions_and_health",
    "tests/test_infer.py::test_stepwise_decode_matches_full_forward",
    "tests/test_kv_quant.py::test_cached_forward_tracks_exact_forward",
    "tests/test_kv_quant.py::test_continuous_engine_with_int8_cache",
    "tests/test_kv_quant.py::test_generator_with_int8_cache_deterministic",
    "tests/test_logprobs.py::test_engine_logprobs_greedy_top1_is_chosen",
    "tests/test_logprobs.py::test_logprobs_do_not_change_tokens",
    "tests/test_logprobs.py::test_server_logprobs_json",
    "tests/test_model.py::test_causality",
    "tests/test_model.py::test_lora_starts_identical_to_base",
    "tests/test_model.py::test_moe_forward",
    "tests/test_model.py::test_remat_policies_preserve_loss_and_grads[attn]",
    "tests/test_model.py::test_remat_policies_preserve_loss_and_grads[dots]",
    "tests/test_model.py::test_remat_policies_preserve_loss_and_grads[full]",
    "tests/test_model.py::test_remat_policies_preserve_loss_and_grads[none]",
    "tests/test_model.py::test_segment_isolation",
    "tests/test_multilora.py::test_adapter_selection_matches_single_adapter_models",
    "tests/test_multilora.py::test_server_routes_model_field_to_adapter",
    "tests/test_multilora.py::test_zero_adapter_equals_base_model",
    "tests/test_paged.py::test_generated_pages_reused_across_turns",
    "tests/test_paged.py::test_paged_automatic_prefix_reuse",
    "tests/test_paged.py::test_paged_cancel_frees_pages",
    "tests/test_paged.py::test_paged_capacity_exceeds_contiguous_equivalent",
    "tests/test_paged.py::test_paged_chunked_prefill_matches_unchunked",
    "tests/test_paged.py::test_paged_matches_lockstep_generator_greedy",
    "tests/test_paged.py::test_paged_on_mesh_matches_single_device",
    "tests/test_paged.py::test_paged_pool_exhaustion_queues_and_recovers",
    "tests/test_paged.py::test_paged_register_prefix_is_a_warm_hint",
    "tests/test_paged.py::test_paged_sampled_seed_reproducible",
    "tests/test_pipeline.py::test_pipeline_forward_matches_scan[2]",
    "tests/test_pipeline.py::test_pipeline_forward_matches_scan[4]",
    "tests/test_pipeline.py::test_pipeline_microbatch_count",
    "tests/test_pipeline.py::test_pipeline_moe_aux_matches",
    "tests/test_pipeline.py::test_pipeline_train_step_matches_single_device",
    "tests/test_podserve.py::test_pod_continuous_concurrent_and_streaming",
    "tests/test_podserve.py::test_pod_continuous_matches_plain_engine",
    "tests/test_podserve.py::test_pod_generate_matches_direct",
    "tests/test_podserve.py::test_server_continuous_via_pod",
    "tests/test_profiling.py::test_metrics_jsonl_stream",
    "tests/test_profiling.py::test_trainer_profile_config_end_to_end",
    "tests/test_quant.py::test_quantized_forward_close_to_float",
    "tests/test_quant.py::test_quantized_generator_and_continuous_agree",
    "tests/test_quant.py::test_quantized_moe_forward",
    "tests/test_recovery.py::test_fault_propagates_without_restarts",
    "tests/test_recovery.py::test_no_restart_when_resume_disabled",
    "tests/test_recovery.py::test_no_restart_without_checkpointing",
    "tests/test_recovery.py::test_restart_budget_exhausted",
    "tests/test_recovery.py::test_sigkill_drill_process_supervisor_resumes",
    "tests/test_recovery.py::test_supervisor_recovers_from_injected_fault",
    "tests/test_ring_attention.py::test_grads_flow_through_ring",
    "tests/test_ring_attention.py::test_matches_full_attention[True]",
    "tests/test_speculative.py::test_int8_kv_cache_composes",
    "tests/test_speculative.py::test_matches_lockstep_greedy[1]",
    "tests/test_speculative.py::test_matches_lockstep_greedy[4]",
    "tests/test_speculative.py::test_matches_lockstep_greedy[8]",
    "tests/test_speculative.py::test_matches_lockstep_on_repetitive_prompt",
    "tests/test_speculative.py::test_single_and_empty_prompts",
    "tests/test_stop_sequences.py::test_server_finish_reason_length",
    "tests/test_stop_sequences.py::test_server_stop_truncates_and_reports_stop",
    "tests/test_train.py::test_alternate_optimizers_train[adafactor]",
    "tests/test_train.py::test_alternate_optimizers_train[lion]",
    "tests/test_train.py::test_alternate_optimizers_train[sgd]",
    "tests/test_train.py::test_bf16_adam_mu",
    "tests/test_train.py::test_dp_and_fsdp_agree",
    "tests/test_train.py::test_grad_accum_matches_full_batch",
    "tests/test_train.py::test_local_validation_eval",
    "tests/test_train.py::test_lora_freezes_base",
    "tests/test_train.py::test_loss_decreases_dp",
    "tests/test_train.py::test_loss_decreases_fsdp_tp",
    "tests/test_train.py::test_multi_step_matches_single_steps",
    "tests/test_train.py::test_train_step_attention_impls",
    "tests/test_ulysses.py::test_full_train_step_with_ulysses",
    "tests/test_ulysses.py::test_grads_flow_through_all_to_all",
    "tests/test_ulysses.py::test_matches_full_attention[True]",
    "tests/test_ulysses.py::test_segment_ids_packing",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
