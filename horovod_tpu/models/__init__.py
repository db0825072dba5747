from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.vgg import VGG, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.mlp import MLP  # noqa: F401
from horovod_tpu.models.transformer import (  # noqa: F401
    BlockSpec,
    ChunkSummaryAttention,
    DifferentialAttention,
    GroupedAttention,
    LatentAttention,
    Mamba2,
    MemoryUnit,
    NextTokenModule,
    Rotary,
    SelectiveScan,
    ShortConv,
    TopkExperts,
    Transformer,
    TransformerConfig,
    apply_with_aux,
    lm_loss,
    looped_lm_loss,
    multi_offset_lm_loss,
)
