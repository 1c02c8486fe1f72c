from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.fused_decode import fused_decode_layer
from ray_tpu.ops.ulysses import ulysses_attention

__all__ = ["dot_product_attention", "fused_decode_layer",
           "ulysses_attention"]
