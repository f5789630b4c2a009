"""Reference model zoo (reference: apex/transformer/testing/standalone_gpt.py,
standalone_bert.py, examples/imagenet, apex/mlp, apex/fused_dense).

These are the framework's example applications *and* its benchmark/test
vehicles, the role standalone_gpt.py plays for the reference test suite.
"""

from apex_tpu.models.bert import BertConfig, BertModel  # noqa: F401
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: F401
from apex_tpu.models.instella import InstellaConfig, InstellaModel  # noqa: F401
from apex_tpu.models.kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    KimiLinearModel,
)
from apex_tpu.models.lfm2 import Lfm2Config, Lfm2Model  # noqa: F401
from apex_tpu.models.mlp import MLP  # noqa: F401
from apex_tpu.models.fused_dense import FusedDense, FusedDenseGeluDense  # noqa: F401
from apex_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
