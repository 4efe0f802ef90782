from attacking_federate_learning_tpu.models.base import (  # noqa: F401
    MODELS, Model, get_model
)

# Import for registry side effects.
from attacking_federate_learning_tpu.models import mnist  # noqa: F401
from attacking_federate_learning_tpu.models import mnist_cnn  # noqa: F401
from attacking_federate_learning_tpu.models import cifar10  # noqa: F401
from attacking_federate_learning_tpu.models import wideresnet  # noqa: F401
from attacking_federate_learning_tpu.models import resnet  # noqa: F401
from attacking_federate_learning_tpu.models import sequence  # noqa: F401
