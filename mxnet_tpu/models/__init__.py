"""Model zoo: symbol builders for the reference's headline workloads.

Capability parity targets (SURVEY.md §7 / BASELINE.md): MLP + LeNet
(MNIST), ResNet-18/34/50/101/152 + ResNeXt, Inception-v3/BN, AlexNet,
VGG (ImageNet), LSTM language models (PTB), and a transformer with ring
attention (the TPU-native long-context flagship — beyond reference
parity, standing in for its model-parallel LSTM). ``olmoe`` is
OLMoE-1B-7B (64 SwiGLU experts, top-8, dropless) as an ``mx.sym`` graph
of the ``RMSNorm`` / ``RoPE`` / ``Attention`` / ``TopKMoE`` ops, trained
by ``Module.fit``; ``olmoe_reference`` is its plain float32 reference.
``mimo_v2`` is MiMo-V2-Flash (window-128 and full attention over grouped
heads, 256 sigmoid-routed experts) from the same ops, whole or as one
chip's share of its layers, with ``mimo_v2_reference`` beside it.
``kanana2`` is Kanana-2-30B-A3B (latent attention, shared experts beside
128 sigmoid-routed ones), whole or as a share, with
``kanana2_reference``. ``nemotron_h`` is Nemotron-3-Nano-30B-A3B
(Mamba-2 state-space layers, un-gated relu² experts and NoPE grouped
attention in one-mixer blocks), whole or as a share, with
``nemotron_h_reference``. ``olmo_hybrid`` is Olmo-Hybrid-7B (gated
delta-rule linear attention three to one beside full attention, dense
SwiGLU, blocks that norm a sub-layer's output), whole or as a pipeline
stage, with ``olmo_hybrid_reference``. ``lfm2`` is LFM2-24B-A2B (gated
short-convolution layers three to one beside grouped attention on heads
of 64, a dense SwiGLU then 64 sigmoid-routed experts, a head tied to the
embedding), whole or as a share, with ``lfm2_reference``. ``falcon_h1``
is Falcon-H1-34B (Mamba-2 and grouped attention side by side in every
block off one norm, fourteen fixed multipliers, a dense SwiGLU), whole or
as one of the chips that share a layer by tensor parallelism, with
``falcon_h1_reference``. ``kimi_linear`` is Kimi-Linear-48B-A3B (Kimi
Delta Attention, a delta rule whose decay is a vector a head, three to
one beside latent attention without a rotary embedding, a dense SwiGLU
then one shared and 256 sigmoid-routed experts), whole or as a share,
with ``kimi_linear_reference``. ``afmoe`` is Trinity-Mini (attention
under per-head norms whose output passes a sigmoid gate, a 2,048-key
window three to one beside full attention without a rotary embedding,
four norms a block, a dense SwiGLU then one shared and 128
sigmoid-routed experts, a muP multiplier on the embedding), whole or as
a share, with ``afmoe_reference``. ``dots3`` is dots3-note-prev (latent
attention with a query latent in two geometries: full layers whose
``KeyIndexer`` chooses the 2,048 best keys a query, one in four beside
layers under a 513-key window over a latent of their own, a sigmoid gate
a head on both, a dense SwiGLU then one shared and 256 sigmoid-routed
experts), whole or as a share, with ``dots3_reference``.
``solar_open2`` is Solar-Open2-250B (Kimi Delta Attention with write
strengths up to 2, three to one beside gated grouped attention without a
rotary embedding, one shared and 320 sigmoid-routed experts in every
layer), whole or as a share of its experts, of both mixers' heads and of
its vocabulary, with ``solar_open2_reference``. ``ouro`` is Ouro-2.6B (a
looped language model: a Llama-shaped stack under four norms a block run
four times over ONE set of weights, the final norm, an exit gate and the
head after every pass, the exit-weighted loss; every weight one
``Variable`` read at four depths), whole or as a pipeline stage, with
``ouro_reference``. ``keye_vl2`` is Keye-VL-2.0-30B-A3B's language model
(grouped attention under per-head norms that reads only the 2,048 keys a
16-head ``KeyIndexer`` picks, in every layer, over 128 softmax-routed
experts), whole or as a share, with ``keye_vl2_reference``.
``minicpm_sala`` is MiniCPM-SALA (Lightning linear attention with a fixed
decay a head in three layers of four, InfLLM-V2 block-sparse attention that
chooses 64 blocks of 64 keys from mean-pooled keys in the fourth, MiniCPM's
scaling), whole or as one of two chips sharing a layer, with
``minicpm_sala_reference``; ``lm_blocks`` holds what the LM symbols share.
"""
from .mlp import get_symbol as mlp
from .lenet import get_symbol as lenet
from .alexnet import get_symbol as alexnet
from .resnet import get_symbol as resnet
from .inception_v3 import get_symbol as inception_v3
from .inception_bn import get_symbol as inception_bn
from .inception_resnet_v2 import get_symbol as inception_resnet_v2
from .googlenet import get_symbol as googlenet
from .resnext import get_symbol as resnext
from .vgg import get_symbol as vgg
from .lstm import lstm_unroll, BucketingLSTMModel
from .transformer import transformer_lm
from . import (afmoe, afmoe_reference, dots3, dots3_reference, falcon_h1,
               falcon_h1_reference, kanana2, kanana2_reference, kimi_linear,
               keye_vl2, keye_vl2_reference, kimi_linear_reference, lfm2,
               lfm2_reference, mimo_v2, mimo_v2_reference, minicpm_sala,
               minicpm_sala_reference, nemotron_h,
               nemotron_h_reference, olmo_hybrid, olmo_hybrid_reference,
               olmoe, olmoe_reference, ouro, ouro_reference, solar_open2,
               solar_open2_reference)
