"""Model zoo mirroring the reference's benchmark configurations
(reference: benchmark/fluid/models/ — mnist, resnet, vgg, machine
translation / transformer, stacked_dynamic_lstm, se_resnext).

Each builder constructs its graph into the CURRENT default main/startup
programs (use fluid.program_guard to redirect) and returns a ModelSpec with
the feed names, loss/metric variables, and a synthetic-batch generator for
benchmarking without datasets.
"""

from .common import ModelSpec  # noqa: F401
from .mnist import lenet5  # noqa: F401
from .resnet import resnet_cifar10, resnet_imagenet  # noqa: F401
from .alexnet import alexnet  # noqa: F401
from .googlenet import googlenet  # noqa: F401
from .vgg import vgg16, vgg19  # noqa: F401
from .transformer import transformer, TransformerConfig  # noqa: F401
from .looped_decoder import looped_decoder, LoopedDecoderConfig  # noqa: F401
from .expert_decoder import expert_decoder, ExpertDecoderConfig  # noqa: F401
from .sparse_decoder import sparse_decoder, SparseDecoderConfig  # noqa: F401
from .windowed_decoder import (  # noqa: F401
    windowed_decoder, WindowedDecoderConfig)
from .compressed_decoder import (  # noqa: F401
    compressed_decoder, CompressedDecoderConfig)
from .hybrid_linear_decoder import (  # noqa: F401
    hybrid_linear_decoder, HybridLinearDecoderConfig)
from .hyper_expert_decoder import (  # noqa: F401
    hyper_expert_decoder, HyperExpertDecoderConfig)
from .eva_decoder import eva_decoder, EvaDecoderConfig  # noqa: F401
from .sambay_decoder import (  # noqa: F401
    sambay_decoder, SambaYDecoderConfig)
from .ssd_hybrid_decoder import (  # noqa: F401
    ssd_hybrid_decoder, SsdHybridDecoderConfig)
from .gated_delta_decoder import (  # noqa: F401
    gated_delta_decoder, GatedDeltaDecoderConfig)
from .stacked_lstm import stacked_dynamic_lstm  # noqa: F401
from .machine_translation import machine_translation  # noqa: F401
from .se_resnext import se_resnext  # noqa: F401
from .deepfm import deepfm  # noqa: F401
