"""Model abstraction and canonical bases (reference: tensor2robot models/)."""

from tensor2robot_tpu.models.model_interface import ModelInterface
from tensor2robot_tpu.models.abstract_model import (
    AbstractT2RModel,
    TrainState,
)
from tensor2robot_tpu.models.regression_model import (
    INFERENCE_OUTPUT,
    RegressionModel,
)
from tensor2robot_tpu.models.classification_model import (
    LOGITS,
    ClassificationModel,
)
from tensor2robot_tpu.models.critic_model import (
    Q_VALUE,
    CriticModel,
)
from tensor2robot_tpu.models.optimizers import (
    create_lr_schedule,
    create_optimizer,
)
from tensor2robot_tpu.models.language_model import (
    LatentAttentionLanguageModel,
    NextTokenLanguageModel,
)
