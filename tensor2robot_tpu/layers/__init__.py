"""Network building blocks (reference: tensor2robot layers/)."""

from tensor2robot_tpu.layers.core import MLP, flatten_and_concat
from tensor2robot_tpu.layers.vision_layers import (
    ConvTower,
    FiLM,
    ImageEncoder,
    SpatialSoftmax,
    spatial_softmax,
)
from tensor2robot_tpu.layers.resnet import (
    BottleneckBlock,
    ResNet,
    ResNetBlock,
    resnet18,
    resnet34,
    resnet50,
)
from tensor2robot_tpu.layers.mdn import (
    MDNHead,
    MDNParams,
    mdn_log_prob,
    mdn_loss,
    mdn_mean,
    mdn_mode,
    mdn_sample,
)
from tensor2robot_tpu.layers.snail import (
    AttentionBlock,
    CausalConv1D,
    DenseBlock,
    SNAIL,
    TCBlock,
)
from tensor2robot_tpu.layers.transformer import (
    CausalTransformer,
    GatedAttention,
    MultiHeadAttention,
    RMSNorm,
    SequenceTrunk,
    TransformerBlock,
)
from tensor2robot_tpu.layers.gated_delta import (
    GatedDeltaNet,
    gated_delta_rule,
)
from tensor2robot_tpu.layers.pipelined_transformer import (
    PipelinedCausalTransformer,
)
