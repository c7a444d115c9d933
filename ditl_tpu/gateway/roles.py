"""Replica roles for disaggregated prefill/decode serving (ISSUE 9).

DistServe (Zhong et al., OSDI'24) and Splitwise (Patel et al., ISCA'24)
make the case that prefill-heavy and decode-heavy serving want DIFFERENT
machine configurations: prefill is a throughput problem (big per-tick
token budgets, large chunks, deep page pools), decode is a latency problem
(many concurrent slots, small budgets so no tick stalls a stream). A
homogeneous fleet forces one compromise config on both; a heterogeneous
fleet lets the router steer each request class to the replicas shaped for
it, which removes prefill/decode interference at the ROUTING layer — on
top of whatever the per-tick token budget (ISSUE 8) already bounds inside
one replica.

Three roles:

- ``hybrid`` — today's default: the base config untouched. A fleet of
  hybrids is exactly the pre-ISSUE-9 fleet.
- ``prefill_heavy`` — fewer decode slots, 4x the prefill chunk, 4x the
  token budget, 2x the page pool: a replica shaped to chew through long
  prompts (batch / best_effort work) without a latency SLO to protect.
- ``decode_heavy`` — 2x the decode slots with the TIGHTEST legal token
  budget (one full decode tick + one chunk of prefill progress): a replica
  shaped so interactive streams never absorb a long co-scheduled prefill.

Everything here is pure stdlib host code over plain numbers and
``ReplicaView`` snapshots — unit-testable without jax, importable by the
gateway (which must stay jax-free) and by launchers alike.
"""

from __future__ import annotations

__all__ = ["ROLES", "handoff_sources", "parse_roles", "role_candidates",
           "role_knobs"]

ROLES = ("hybrid", "prefill_heavy", "decode_heavy")


def parse_roles(spec: str, n_replicas: int) -> list[str]:
    """Parse a comma-separated role spec (``"prefill_heavy,decode_heavy"``)
    into one role per replica. Shorter specs pad with ``hybrid`` (the
    un-opinionated default); longer specs are a config error, not a silent
    truncation. Empty spec = all hybrid (the homogeneous fleet)."""
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    roles = [r.strip() for r in spec.split(",") if r.strip()] if spec else []
    for r in roles:
        if r not in ROLES:
            raise ValueError(f"unknown replica role {r!r} (one of {ROLES})")
    if len(roles) > n_replicas:
        raise ValueError(
            f"{len(roles)} roles specified for {n_replicas} replica(s): "
            f"{roles}"
        )
    return roles + ["hybrid"] * (n_replicas - len(roles))


def role_knobs(
    role: str,
    *,
    n_slots: int,
    decode_chunk: int = 8,
    prefill_chunk: int = 0,
    token_budget: int = 0,
) -> dict:
    """Derive one replica's engine knobs from its role and the fleet's base
    config. Returns ``{"n_slots", "prefill_chunk", "token_budget",
    "pages_scale"}`` — concrete values for the first three (the scaling
    preserves every engine invariant: budgets cover a full decode tick,
    chunk multiples of the page size stay multiples), and a multiplier for
    whatever page-pool size the caller would otherwise use (the pool's
    default is derived from slot count, which these knobs change).

    A base of 0 for ``prefill_chunk``/``token_budget`` means "feature off"
    and stays 0 — a role must not silently arm chunking or budgeting the
    operator disabled (whole-prompt prefill IS the biggest chunk there is,
    which suits prefill_heavy fine)."""
    if role not in ROLES:
        raise ValueError(f"unknown replica role {role!r} (one of {ROLES})")
    if n_slots < 1:
        raise ValueError(f"n_slots must be >= 1, got {n_slots}")
    if role == "hybrid":
        return {"n_slots": n_slots, "prefill_chunk": prefill_chunk,
                "token_budget": token_budget, "pages_scale": 1.0}
    if role == "prefill_heavy":
        slots = max(1, n_slots // 2)
        chunk = prefill_chunk * 4
        budget = 0 if token_budget == 0 else max(
            token_budget * 4, slots * decode_chunk + max(chunk, 1)
        )
        return {"n_slots": slots, "prefill_chunk": chunk,
                "token_budget": budget, "pages_scale": 2.0}
    # decode_heavy: double the slots, keep the chunk, and shrink the budget
    # to the tightest legal value — one full decode tick plus one chunk of
    # prefill progress (the engine's at-least-one-chunk rule needs that
    # headroom; anything less would reject at construction).
    slots = n_slots * 2
    budget = 0 if token_budget == 0 else (
        slots * decode_chunk + max(prefill_chunk, 1)
    )
    return {"n_slots": slots, "prefill_chunk": prefill_chunk,
            "token_budget": budget, "pages_scale": 1.0}


def role_candidates(
    candidates,
    slo_class: str | None,
    prompt_tokens: int = 0,
    long_prompt_tokens: int = 0,
):
    """Class -> role steering over ``ReplicaView`` candidates, layered
    UNDER whatever routing policy runs next (the policy picks within the
    returned set; affinity keeps its ring semantics on the subset).

    - interactive (and unclassed — the engine's default class) requests
      avoid ``prefill_heavy`` replicas: their big budgets exist to absorb
      long prefills, exactly the interference a latency-sensitive stream
      must not sit behind;
    - batch / best_effort requests whose prompt is long (>=
      ``long_prompt_tokens`` whitespace tokens; 0 = all of them) avoid
      ``decode_heavy`` replicas: a long prefill there would stall the very
      streams the role protects;
    - a homogeneous (all-hybrid) candidate set is returned untouched, and
      an EMPTY preferred set falls back to the full candidate set — a dead
      prefill_heavy replica degrades the fleet to hybrid serving; no
      request class is ever unroutable while any replica lives.
    """
    candidates = list(candidates)
    roles = {getattr(v, "role", "hybrid") for v in candidates}
    if roles <= {"hybrid"}:
        return candidates
    if slo_class in (None, "", "interactive"):
        pref = [v for v in candidates
                if getattr(v, "role", "hybrid") != "prefill_heavy"]
    elif (slo_class in ("batch", "best_effort")
          and (long_prompt_tokens <= 0
               or prompt_tokens >= long_prompt_tokens)):
        pref = [v for v in candidates
                if getattr(v, "role", "hybrid") != "decode_heavy"]
    else:
        pref = candidates
    return pref or candidates


def handoff_sources(candidates, decode_id: str):
    """The replicas eligible to run a prefill on the DECODE replica's
    behalf for a KV handoff (ISSUE 13): live ``prefill_heavy`` views that
    serve the /internal KV endpoints (``kv_handoff`` health flag), minus
    the chosen decode replica itself. Empty means the relay leg has
    nobody to ship from — the gateway's orchestration skips the handoff
    and the decode replica prefills locally, exactly the hybrid-serving
    degradation ``role_candidates`` guarantees for routing."""
    return [
        v for v in candidates
        if getattr(v, "role", "hybrid") == "prefill_heavy"
        and getattr(v, "kv_handoff", False)
        and v.id != decode_id
    ]
