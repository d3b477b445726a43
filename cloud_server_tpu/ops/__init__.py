from cloud_server_tpu.ops.norms import rms_norm  # noqa: F401
from cloud_server_tpu.ops.rope import (  # noqa: F401
    apply_rope, rope_frequencies, rope_table)
from cloud_server_tpu.ops.activations import gated, swiglu  # noqa: F401
from cloud_server_tpu.ops.attention import causal_attention  # noqa: F401
