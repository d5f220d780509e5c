"""NN modules (flax) — parity surface of ``unicore/modules/__init__.py:1-9``."""

from unicore_tpu.ops import layer_norm as layer_norm_fn  # noqa: F401
from unicore_tpu.ops import softmax_dropout  # noqa: F401

from .layer_norm import LayerNorm  # noqa: F401
from .rotary import (  # noqa: F401
    RotarySpec,
    apply_rotary,
    apply_rotary_qk,
    apply_rotary_spec,
    rotary_cos_sin,
)
from .multihead_attention import (  # noqa: F401
    CrossMultiheadAttention,
    SelfMultiheadAttention,
    bert_init,
)
from .transformer_encoder import (  # noqa: F401
    TransformerEncoder,
    TransformerEncoderLayer,
    make_rp_bucket,
    relative_position_bucket,
)
from .transformer_decoder import (  # noqa: F401
    TransformerDecoder,
    TransformerDecoderLayer,
)
from .pattern_decoder import (  # noqa: F401
    AttentionSpec,
    ExpertFFN,
    ExpertSpec,
    FullAttentionMixer,
    GatedFFN,
    LatentAttentionMixer,
    LatentSpec,
    LinearAttentionMixer,
    PatternDecoder,
    PatternDecoderLayer,
    RMSNorm,
    ShortConvMixer,
)
from .triangle_attention import (  # noqa: F401
    EvoformerPairBlock,
    PairTransition,
    TriangleAttention,
    TriangleMultiplication,
)
from .msa_attention import (  # noqa: F401
    EvoformerBlock,
    MSAColumnAttention,
    MSARowAttentionWithPairBias,
    MSATransition,
    OuterProductMean,
)
from .structure_module import (  # noqa: F401
    BackboneUpdate,
    InvariantPointAttention,
    StructureModule,
    StructureModuleLayer,
)
