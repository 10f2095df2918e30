"""The one table of device-scope names.

Each constant is entered as ``jax.named_scope(NAME)`` at one layer boundary
of the model or the engine, and so becomes a component of the ``op_name`` of
every HLO operation traced under it (``jit(_tick)/layers/while/body/ssd/...``;
a transformation wraps the component: ``transpose(jvp(ssd))``,
``checkpoint/rematted_computation/ssd``).  A device trace keeps the
``op_name``, so device time can be filed by these names where fusion numbers
change with every edit.  Scopes are metadata only: they change no operation,
no fusion and no jitted program's name.

Constants only, and no import of JAX: the program, the tests,
``docs/OBSERVABILITY.md`` and the benchmark's metric files spell the names
alike.  An operation is filed under the *innermost* name of this table in
its path; one whose path holds ``layers`` and nothing deeper is the layer
scan's own (its slices of the stacked weights, its stacked write-backs).
"""

EMBED = "embed"  # models/lm.py _embed
LAYERS = "layers"  # every lax.scan over blocks, the unrolled stacks, the SSM tick's sub-step scan
ATTN_LAYERS = "attn_layers"  # the scans that carry the KV page pool (the hybrid tick's too)
MIXER_IN_PROJ = "mixer_in_proj"  # mamba2 in_proj and the z/xBC/dt split
CONV = "conv"  # ops/conv.py, both forms
SSD = "ssd"  # ops/ssd.py ssd_chunked, ssd_state_update; the Pallas SSD
CHUNK_LOCAL = "chunk_local"  # children of SSD, ops/ssd.py
STATE_PASSING = "state_passing"
COMBINE_CHUNK_OUTPUTS = "combine_chunk_outputs"
GATE_NORM = "gate_norm"  # the gated RMSNorm after the scan
MIXER_OUT_PROJ = "mixer_out_proj"
ATTN_QKV = "attn_qkv"  # wqkv, split, rope
ATTN_KERNEL = "attn_kernel"  # the attention itself, Pallas or lax
KV_WRITE = "kv_write"  # the lax scatter of K/V into the page pool
ATTN_OUT = "attn_out"
LM_HEAD_LOSS = "lm_head_loss"  # final norm, head, loss (blocked: fwd and bwd)
POOL_SELECT = "pool_select"  # the tick's hold of parked slots' logits; insert/evict/stash
SAMPLE = "sample"  # the tick's top-k and draw
OPTIMIZER = "optimizer"  # gradient accumulation, clip, AdamW, apply

ALL = (
    EMBED, LAYERS, ATTN_LAYERS, MIXER_IN_PROJ, CONV, SSD, CHUNK_LOCAL,
    STATE_PASSING, COMBINE_CHUNK_OUTPUTS, GATE_NORM, MIXER_OUT_PROJ,
    ATTN_QKV, ATTN_KERNEL, KV_WRITE, ATTN_OUT, LM_HEAD_LOSS, POOL_SELECT,
    SAMPLE, OPTIMIZER,
)
