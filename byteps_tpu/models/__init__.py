"""Model families for byteps_tpu.

The reference ships benchmark/example models via torchvision/gluon model
zoos (reference: example/pytorch/benchmark_byteps.py uses
torchvision.models, example/mxnet uses gluon model_zoo); this package is
the in-tree TPU-native equivalent: a transformer LM family (flagship —
BERT-large is the reference's headline benchmark, README.md:38-46), a CNN
family (ResNet/VGG — docs/performance.md benchmarks), and an MNIST MLP.

Beside them, imported by name where they are used (docs/models.md): the
decoders the benchmark runs as one chip's share of a deployment, `afmoe`
(layers of more than one kind, sparse experts beside a shared one),
`mellum` (sparse experts in every layer, windowed and YaRN full
attention), `keye` (attention over the keys a learned indexer selects,
rotary positions in three streams), `granite_hybrid` (state-space
scans beside attention), `nemotron_h` (layers that are one part each:
a Mamba-2 mixer in 8 groups, squared-ReLU experts, grouped attention;
stacked by kind), `joyai` (latent attention, a sigmoid router with a
shared expert behind one dense layer, a multi-token-prediction module),
`lfm2` (a doubly gated short convolution or grouped attention as a
layer's mixer, a dense SwiGLU or sigmoid-routed experts as its
feed-forward, scanned a run of one kind at a time) and `kimi_linear` (a
delta rule whose state decays a key channel at a time, the chunked
kernels of `ops/kda.py`, three such layers to every one of latent
attention without positions; 8-of-256 sigmoid-routed experts round a
shared one) and `sdar` (block-diffusion training: a clean and a noised
copy of every sequence under the flash kernels' mask of three regions, a
masked 1/t-weighted loss on the noised rows) and `ouro` (a LOOPED
decoder: a stack of sandwich-normed layers walked four times with the
same weights as one scan of layer applications, an exit gate after every
walk, the expected cross-entropy over the exit walk as one streamed head
call under differentiated weights).  Their attention calls come
from one table, `afmoe._ATTENTION`: `sliding_attention`,
`full_attention`, `selected_attention`, `block_diffusion`.
"""

from . import transformer
from . import cnn
from . import mlp

from .transformer import (
    TransformerConfig, get_config as get_transformer_config,
    init_params as init_transformer, forward as transformer_forward,
    loss_fn as transformer_loss,
)
from .cnn import create_cnn, cnn_loss_fn
from .mlp import (
    init_params as init_mlp, forward as mlp_forward, loss_fn as mlp_loss,
)

__all__ = [
    "transformer", "cnn", "mlp",
    "TransformerConfig", "get_transformer_config", "init_transformer",
    "transformer_forward", "transformer_loss",
    "create_cnn", "cnn_loss_fn",
    "init_mlp", "mlp_forward", "mlp_loss",
]
