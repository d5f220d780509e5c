"""Functional TPU ops (L0/L1 boundary).

Each op ships two implementations:

- a plain-``jnp`` reference implementation — the behavioral spec and test
  oracle (the analogue of the reference's eager-PyTorch fallbacks, e.g.
  ``unicore/modules/softmax_dropout.py:139-144``);
- a Pallas (Mosaic) TPU kernel — the perf tier, the analogue of the
  reference's six CUDA extensions (``setup.py:112-202``).

Selection is automatic: the Pallas path is used on TPU when the shapes are
eligible, the ``jnp`` path otherwise.  ``set_kernel_backend`` forces one for
testing.
"""

from .backend import get_kernel_backend, kernel_backend, set_kernel_backend  # noqa: F401
from .layer_norm import layer_norm, layer_norm_reference  # noqa: F401
from .softmax_dropout import softmax_dropout, softmax_dropout_reference  # noqa: F401
from .dropout import dropout  # noqa: F401
from .fused_cross_entropy import (  # noqa: F401
    fused_linear_cross_entropy, linear_nll_reference,
)
from .gated_delta_rule import gated_delta_rule, short_conv  # noqa: F401
from .rounding import fp32_to_bf16_sr, fp32_to_bf16_sr_reference  # noqa: F401
from .multi_tensor import l2_norm  # noqa: F401
