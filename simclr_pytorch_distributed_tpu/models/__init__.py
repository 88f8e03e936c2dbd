from simclr_pytorch_distributed_tpu.models.resnet import (  # noqa: F401
    MODEL_DICT,
    ResNet,
    build_encoder,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
)
from simclr_pytorch_distributed_tpu.models.heads import (  # noqa: F401
    LinearClassifier,
    SupCEResNet,
    SupConResNet,
    infer_architecture_from_variables,
)
from simclr_pytorch_distributed_tpu.models.token_encoder import (  # noqa: F401
    TOKEN_ENCODERS,
    TokenEncoder,
)
from simclr_pytorch_distributed_tpu.models.norm import CrossReplicaBatchNorm  # noqa: F401
