"""The stable names of the work in every device program: what a profiler
trace, ``benchmarks/layer_metrics/_scopes.py`` and ``PERF_LEDGER.jsonl`` call
the parts of a step. A refactor may move the code under a name; it may not
rename it. A scope is a ``jax.named_scope`` path segment (innermost wins);
a kernel is the ``name=`` of its ``pl.pallas_call``, which the TPU compiler
also gives to the instruction.

``layer_scan`` is the loop over the layers, and holds only what no layer
part claims: slicing each layer's weights (and, in a cached forward, its
K/V cache or page pool) out of the stacked arrays, stacking what the layers
return, and what remat saves between forward and backward. ``kv_gather`` is
an explicit read of cached K/V (the contiguous cache's ``read_kv``, a paged
prefill's gather of its context pages); the paged decode program has none,
its kernel reads the pages in place. ``kv_write`` in a paged decode is each
step's write of the new K and V into the tick's tail (a
``dynamic_update_slice`` a layer) and, once a tick, the flush of that tail
into the page pools: the ``kv_flush`` kernel, which sits inside the scope, so
a reader that knows only ``SCOPES`` books it there. A layout copy that the
compiler puts around a write carries no scope at all (the flush's scatter had
eight of the whole pool a tick until PR 29, a fifth of the 7B serving cell's
device time, under no name).

``MOE_SCOPES`` are the parts of an expert layer (``models/moe.py``), all
INSIDE ``mlp``: to a reader that knows only ``SCOPES`` an expert layer's time
is ``mlp``'s, as a dense layer's is; to one that also knows these (innermost
wins) ``mlp`` keeps only the norm. ``moe_router``: the router's matmul and
softmax; ``moe_dispatch``: top-k, the sort by expert and the gather of the
token rows; ``moe_experts``: the three grouped matmuls; ``moe_combine``: the
rows back in token order, weighted and summed.

``MLA_SCOPES`` are the parts of a latent attention sublayer (``models/
mla.py``), each INSIDE the scope of ``SCOPES`` it refines, so a reader that
knows only ``SCOPES`` still books the time: ``mla_q`` (the query's
down-projection, norm, scale, up-projection, rotation and, in decode, the
absorption of ``Wkvb``'s key half) and ``mla_kv`` (the latent's projection,
norm, scale and rotation; in prefill its decompression through ``Wkvb``)
inside ``attn_qkv``, ``mla_kv`` again inside ``attn_out`` for the value half
of the absorption; ``mla_attn`` inside ``attn_core``: a decode step's
attention over the latent pool (the kernel ``mla_paged_attention`` and what
surrounds it). The single latent block's TRAINING form (``models/dsa.py``
without an indexer, a forward without cache) keeps the three names:
``mla_q`` the query's one matrix and rotation, ``mla_kv`` the latent's
projection, norm and rotation AND the keys' and values' decompression
through ``Wkvb`` with the rotary key's broadcast over the heads, ``mla_attn``
inside ``attn_core`` the flash kernels at two widths (``KERNELS``). The latent's write into the tick's tail and the tail's flush
are ``kv_write``. ``moe_zero`` (``MOE_ZERO_SCOPES``), inside ``moe_combine``:
the zero-compute experts' weighted identity.

``SSM_SCOPES`` are the parts of a Mamba-2 state-space mixer (``models/
ssm.py``), each INSIDE the scope of ``SCOPES`` it refines: ``ssm_in`` (the
input projection, the causal convolution and its activation, the step sizes)
inside ``attn_qkv``; ``ssm_scan`` (a whole sequence's chunked scan, or a
decode step's read of the row's state, its update, ``S C`` and the write
back) inside ``attn_core``; ``ssm_out`` (the gate, the norm and the output
projection) inside ``attn_out``. The seat of a slot's state after a prefill
is ``kv_write``. ``ssd_step`` (``SSM_KERNELS``), inside ``ssm_scan``: the
decode step's update of the live rows' states in place (``ops/ssd.py``).

``RET_SCOPES`` are the parts of a power-retention mixer (``models/
retention.py``), each INSIDE the scope of ``SCOPES`` it refines: ``ret_in``
(the projections, the head norms, the rotation, the gate) inside
``attn_qkv``; ``ret_state`` (a prefill call's scan, or a decode step's
feature maps, the sum of keys, the read of the live rows' states, the tick's
held tokens' own sums, at a tick's last step their fold into the states and
the write back, and the division) inside ``attn_core``; ``ret_out`` (the
output projection) inside ``attn_out``. The seat of a slot's state after a
prefill is ``kv_write``. ``RET_KERNELS``, inside ``ret_state``: ``ret_step``,
a tick's last step (the held tokens folded into the live rows' states in
place, and the read-out), and ``ret_step_read``, every other step (the
read-out alone, nothing written back) (``ops/retention.py``).

``DSA_SCOPES`` are the parts of DeepSeek-V3.2's sparse attention (``models/
dsa.py``), each INSIDE a scope of ``SCOPES``: ``dsa_index`` is the lightning
indexer, inside ``attn_qkv`` its projections (the index queries from the
query latent, the token's index key with its LayerNorm and rotation, the
heads' weights) and inside ``attn_core`` its scores of a query against every
cached index key: in a paged decode step on the TPU the kernel
``dsa_index_scores`` (``DSA_KERNELS``, ``ops/dsa_index.py``: each live row's
pages of the index pool scored where they lie) and, around it, the small
einsum over the tick's tail and the concatenation of the two; in a prefill,
and off the TPU, a gather of the keys and two einsums; ``dsa_select``, inside
``attn_core``: the top-k of those scores and the page-table arithmetic that
turns positions into pool rows;
``dsa_gather``, inside ``attn_core``: the read of the selected latent
entries out of the pool (a decode step) or the prefill's row. The attention
over what was gathered is ``mla_attn``, the projections around it ``mla_q``
and ``mla_kv``, the writes of both entries ``kv_write``. ``moe_shared``
(``MOE_SHARED_SCOPES``), inside ``mlp``: the shared expert's dense SwiGLU FFN
(``models/moe.py``).

``SWA_SCOPES``, inside ``attn_core``, tell a WINDOW attention layer's
attention from a FULL layer's in a stack that has both (``models/swa.py``):
``attn_window`` and ``attn_full`` hold the layer's attention itself (in a
decode step the kernel ``paged_attention`` over that kind's page pool, walking
that kind's work list; in a prefill the masked scores over that kind's
context row) and the output gate's product. A reader that knows only
``SCOPES`` / ``KERNELS`` books both to ``attn_core`` / ``paged_attention``.

``attn_steps`` (``ATTN_SCOPES``), inside ``attn_core``: the work list of the
paged decode kernels (``ops/paged_attention.py`` ``decode_steps``), built
once a decode program in front of its scan; a reader that knows only
``SCOPES`` books its few microseconds a tick to ``attn_core``. A step of the
K/V kernel's list is a group of ``pages_a_step`` pages (two where a page's
keys and values are half a MiB, one at 1 MiB and more); the kernel keeps the
name ``paged_attention`` whatever a step takes, and the counts the readers
take from the ``engine.tick`` spans (``window_pages_walked``,
``full_pages_walked``) are PAGES, from the rows' positions."""

SCOPES = (
    "embed",
    "attn_qkv",
    "attn_core",
    "attn_out",
    "mlp",
    "layer_scan",
    "lm_head",
    "loss",
    "optimizer",
    "kv_gather",
    "kv_write",
    "sample",
)

MOE_SCOPES = (
    "moe_router",
    "moe_dispatch",
    "moe_experts",
    "moe_combine",
)

MLA_SCOPES = (
    "mla_q",
    "mla_kv",
    "mla_attn",
)

MOE_ZERO_SCOPES = ("moe_zero",)

MOE_SHARED_SCOPES = ("moe_shared",)

DSA_SCOPES = (
    "dsa_index",
    "dsa_select",
    "dsa_gather",
)

SSM_SCOPES = (
    "ssm_in",
    "ssm_scan",
    "ssm_out",
)

RET_SCOPES = (
    "ret_in",
    "ret_state",
    "ret_out",
)

ATTN_SCOPES = ("attn_steps",)

SWA_SCOPES = ("attn_window", "attn_full")

# The decode step's state update (``ops/ssd.py``), inside ``ssm_scan``.
SSM_KERNELS = ("ssd_step",)

# The decode step over the live rows' states (``ops/retention.py``), inside
# ``ret_state``: a tick's last step, which folds the tick's held tokens into
# the state in place and reads it out; and every other step, which only reads.
RET_KERNELS = ("ret_step", "ret_step_read")

# Decode attention over a latent page pool (``ops/mla_attention.py``), inside
# ``mla_attn``.
MLA_KERNELS = ("mla_paged_attention",)

# A decode step's index scores over the row's pages of the index pool
# (``ops/dsa_index.py``), inside ``attn_core/dsa_index``.
DSA_KERNELS = ("dsa_index_scores",)

# The grouped matmul of ``moe_experts`` on one TPU chip is the library's
# kernel (``jax.experimental.pallas.ops.tpu.megablox``): these are ITS names,
# forward and the transposed product of the backward pass.
MOE_KERNELS = ("gmm", "tgmm")

# The tick's flush (``ops/kv_flush.py``), inside ``kv_write``. A tuple of its
# own, as ``MOE_KERNELS``: ``benchmarks/layer_metrics/_scopes.py`` holds its
# table equal to ``SCOPES`` / ``KERNELS``.
CACHE_KERNELS = ("kv_flush",)

KERNELS = (
    "flash_fwd",
    "flash_bwd_dq",
    "flash_bwd_dkv",
    "paged_attention",
    "mlp_bwd_act",
    "mlp_bwd_wgu",
    "proj_bwd",
)
