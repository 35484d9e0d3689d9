"""deepspeed_tpu.ops — Pallas kernels + registry (reference: deepspeed/ops,
op_builder/, csrc/)."""

from .block_sparse_attention import (TilePlan, block_sparse_attention,
                                     build_tile_plan)
from .decode_attention import decode_attention, reference_decode_attention
from .paged_decode_attention import (paged_decode_attention,
                                     paged_prefill_attention,
                                     reference_paged_attention)
from .flash_attention import flash_attention, make_attention_impl
from .moe_grouped_matmul import (moe_grouped_matmul,
                                 reference_grouped_matmul)
from .kda import (kda_chunk, kda_decode_step, kda_recurrence,
                  reference_kda_decode_step)
from .mamba1 import (mamba1_chunk_scan, mamba1_decode_step,
                     mamba1_recurrence, reference_mamba1_decode_step)
from .mamba2 import (mamba2_chunk, mamba2_decode_step, mamba2_recurrence,
                     reference_mamba2_decode_step)
from .fused_adam import fused_adam_flat, reference_adam_flat
from .fused_lamb import fused_lamb_flat, reference_lamb_flat
from .normalization import fused_layer_norm, reference_layer_norm
from .quant_matmul import (int4_a8_matmul, int4_matmul,
                           int8_a8_matmul, int8_matmul,
                           reference_int4_a8_matmul,
                           quantize_activation_rows, quantize_int4,
                           reference_int8_a8_matmul,
                           reference_int4_matmul, reference_int8_matmul,
                           unpack_int4)
from .quantization import (dequantize_symmetric, fake_quantize,
                           quantize_symmetric, reference_quantize_symmetric)
from .sparse_attention import (BigBirdSparsityConfig,  # noqa: F401
                               BSLongformerSparsityConfig,
                               DenseSparsityConfig, FixedSparsityConfig,
                               LocalSlidingWindowSparsityConfig,
                               LocalSparsityConfig, SparsityConfig,
                               VariableSparsityConfig,
                               make_sparse_attention_impl,
                               sparse_self_attention)
from .spatial import (diffusers_attention, fused_group_norm,
                      reference_group_norm)
from .registry import available_ops, get_op, is_compatible, op_report, register_op

register_op("flash_attention", flash_attention,
            reference=lambda *a, **k: _ref_attn(*a, **k),
            description="FA2-style fused attention fwd+bwd")
register_op("fused_adam", fused_adam_flat, reference=reference_adam_flat,
            description="flat-buffer Adam/AdamW update")
register_op("fused_lamb", fused_lamb_flat, reference=reference_lamb_flat,
            description="flat-buffer LAMB update (per-tensor trust ratio)")
register_op("fused_layer_norm", fused_layer_norm, reference=reference_layer_norm,
            description="fused LayerNorm/RMSNorm")
register_op("quantize_symmetric", quantize_symmetric,
            reference=reference_quantize_symmetric,
            description="int8/int4 group quantization")
register_op("decode_attention", decode_attention,
            reference=reference_decode_attention,
            description="single-query KV-cache decode attention (GQA, alibi)")
register_op("paged_decode_attention", paged_decode_attention,
            reference=reference_paged_attention,
            description="block-table decode attention over the paged arena "
                        "(resident pages only; GQA, alibi)")
register_op("paged_prefill_attention", paged_prefill_attention,
            reference=reference_paged_attention,
            description="chunked-prefill flash attention through the "
                        "serving block table")
register_op("moe_grouped_matmul", moe_grouped_matmul,
            reference=reference_grouped_matmul,
            description="expert matmuls over rows sorted by expert (dropless "
                        "MoE inference; touched experts only)")
register_op("kda_decode_step", kda_decode_step,
            reference=reference_kda_decode_step,
            description="one token of the gated delta rule a decode row, "
                        "the state pool updated in place")
register_op("mamba2_decode_step", mamba2_decode_step,
            reference=reference_mamba2_decode_step,
            description="one token of the Mamba-2 state-space recurrence a "
                        "decode row, the state pool updated in place")
register_op("mamba1_decode_step", mamba1_decode_step,
            reference=reference_mamba1_decode_step,
            description="one token of the Mamba-1 selective scan a decode "
                        "row, the state pool updated in place")
register_op("mamba1_chunk_scan", mamba1_chunk_scan,
            reference=mamba1_recurrence,
            description="a chunk of tokens of the Mamba-1 selective scan, "
                        "a slab of channels a grid step")
register_op("int4_a8_matmul", int4_a8_matmul,
            reference=reference_int4_a8_matmul,
            description="W4A8 GEMM (s8 unpack + s8xs8 MXU)")
register_op("int8_a8_matmul", int8_a8_matmul,
            reference=reference_int8_a8_matmul,
            description="W8A8 GEMM (dynamic act quant, s8xs8 MXU)")
register_op("int8_matmul", int8_matmul, reference=reference_int8_matmul,
            description="weight-only int8 GEMM (in-kernel tile dequant)")
register_op("int4_matmul", int4_matmul, reference=reference_int4_matmul,
            description="weight-only int4 GEMM (nibble-packed, group scales)")
register_op("diffusers_attention", diffusers_attention,
            reference=diffusers_attention,
            description="spatial self/cross attention (flash, non-causal)")
register_op("fused_group_norm", fused_group_norm,
            reference=reference_group_norm,
            description="spatial GroupNorm (diffusers UNet norm, NHWC tokens)")
register_op("block_sparse_attention", block_sparse_attention,
            reference=lambda q, k, v, plan, **kw: _ref_attn(q, k, v),
            description="block-skip sparse flash attention over a "
                        "SparsityConfig tile plan (fwd + custom-VJP bwd)")


def _ref_attn(q, k, v, mask=None, causal=True, **_):
    from ..models.transformer import dot_product_attention

    return dot_product_attention(q, k, v, mask, causal=causal)


__all__ = [
    "TilePlan", "block_sparse_attention", "build_tile_plan",
    "decode_attention", "reference_decode_attention",
    "paged_decode_attention", "paged_prefill_attention",
    "reference_paged_attention",
    "moe_grouped_matmul", "reference_grouped_matmul",
    "kda_chunk", "kda_decode_step", "kda_recurrence",
    "reference_kda_decode_step",
    "mamba2_chunk", "mamba2_decode_step", "mamba2_recurrence",
    "reference_mamba2_decode_step",
    "mamba1_chunk_scan", "mamba1_decode_step", "mamba1_recurrence",
    "reference_mamba1_decode_step",
    "flash_attention", "make_attention_impl", "fused_adam_flat",
    "reference_adam_flat", "fused_lamb_flat", "reference_lamb_flat",
    "fused_layer_norm", "reference_layer_norm",
    "quantize_symmetric", "dequantize_symmetric", "fake_quantize",
    "reference_quantize_symmetric", "int8_matmul", "reference_int8_matmul",
    "int8_a8_matmul", "reference_int8_a8_matmul", "quantize_activation_rows",
    "int4_a8_matmul", "reference_int4_a8_matmul",
    "int4_matmul", "reference_int4_matmul", "quantize_int4", "unpack_int4",
    "SparsityConfig", "DenseSparsityConfig", "FixedSparsityConfig",
    "VariableSparsityConfig", "BigBirdSparsityConfig",
    "BSLongformerSparsityConfig", "LocalSlidingWindowSparsityConfig",
    "LocalSparsityConfig", "sparse_self_attention",
    "make_sparse_attention_impl",
    "diffusers_attention", "fused_group_norm",
    "reference_group_norm", "available_ops", "get_op",
    "is_compatible", "op_report", "register_op",
]
