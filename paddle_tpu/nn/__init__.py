"""paddle_tpu.nn — Layer system and neural-net layers
(reference: python/paddle/nn/, ~19k LoC layer+functional; SURVEY §2.4)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer_base import Layer, ParamAttr  # noqa: F401
from .layer.activation import (CELU, ELU, GELU, GLU, Hardshrink,  # noqa
                               Hardsigmoid, Hardswish, Hardtanh, LeakyReLU,
                               LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6,
                               SELU, Sigmoid, Silu, Softmax, Softplus,
                               Softshrink, Softsign, Swish, Tanh, Tanhshrink,
                               ThresholdedReLU)
from .layer.common import (AlphaDropout, Bilinear, CosineSimilarity,  # noqa
                           Dropout, Dropout2D, Embedding, Flatten, Identity,
                           Linear, Pad1D, Pad2D, Pad3D, PixelShuffle,
                           Unfold, Upsample)
from .layer.container import (LayerDict, LayerList, ParameterList,  # noqa
                              Sequential)
from .layer.conv import (Conv1D, Conv2D, Conv2DTranspose, Conv3D)  # noqa
from .layer.loss import (BCELoss, BCEWithLogitsLoss, CosineEmbeddingLoss,  # noqa
                         CrossEntropyLoss, CTCLoss, HingeEmbeddingLoss,
                         KLDivLoss, L1Loss, MarginRankingLoss, MSELoss,
                         NLLLoss, SmoothL1Loss)
from .layer.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,  # noqa
                         GroupNorm, InstanceNorm1D, InstanceNorm2D,
                         InstanceNorm3D, LayerNorm, LocalResponseNorm,
                         RMSNorm, SpectralNorm, SyncBatchNorm)
from .layer.moe import MoELayer  # noqa: F401
from .layer.pooling import (AdaptiveAvgPool1D, AdaptiveAvgPool2D,  # noqa
                            AdaptiveMaxPool2D, AvgPool1D, AvgPool2D,
                            AvgPool3D, MaxPool1D, MaxPool2D, MaxPool3D)
from .layer.rnn import (BiRNN, GRU, GRUCell, LSTM, LSTMCell, RNN,  # noqa
                        RNNCellBase, SimpleRNN, SimpleRNNCell)
from .layer.transformer import (MultiHeadAttention, Transformer,  # noqa
                                TransformerDecoder, TransformerDecoderLayer,
                                TransformerEncoder, TransformerEncoderLayer)

from .decode import (BeamSearchDecoder, cell_step, dynamic_decode,  # noqa
                     gather_tree)

# -- round-4 parity additions --------------------------------------------
from .layer.activation import LogSigmoid  # noqa: F401,E402
from .layer.common import (Dropout3D, PairwiseDistance,  # noqa: F401,E402
                           UpsamplingBilinear2D, UpsamplingNearest2D)
from .layer.conv import Conv1DTranspose, Conv3DTranspose  # noqa: F401,E402
from .layer.loss import HSigmoidLoss  # noqa: F401,E402
from .layer.pooling import (AdaptiveAvgPool3D,  # noqa: F401,E402
                            AdaptiveMaxPool1D, AdaptiveMaxPool3D)
# gradient-clip classes ride in paddle.nn too (reference nn/__init__.py)
from ..optimizer.clip import (ClipGradByGlobalNorm,  # noqa: F401,E402
                              ClipGradByNorm, ClipGradByValue)
# reference exposes the layer submodules as paddle.nn.<name>
from .layer import (activation, common, conv, loss, norm,  # noqa: F401
                    pooling, rnn)
from .layer import common as extension  # noqa: F401,E402
from .layer import conv as vision  # noqa: F401,E402
from .utils import remove_weight_norm, weight_norm  # noqa: F401,E402
from . import utils as weight_norm_hook  # noqa: F401,E402
from .layer.mla import MLAttention  # noqa: F401,E402
from .layer.looped import LoopedStack, LoopExitGate  # noqa: F401,E402
from .layer.ssm import Mamba2Mixer  # noqa: F401,E402
from .layer.ffn import GatedFFN  # noqa: F401,E402
from .layer.attention import GroupedQueryAttention  # noqa: F401,E402
from .layer.short_conv import ShortConv  # noqa: F401,E402
