"""Model zoo: builders for the BASELINE target configs.

Reference analog: examples/cpp + examples/python (SURVEY.md §2.8) — each
builder constructs the model through the FFModel layer API exactly like the
reference examples do, and (TPU-native addition) can also return a manual
tensor/expert-parallel strategy as node-name -> ShardingView, playing the
role of the reference's strategy files.
"""

from flexflow_tpu.models.mlp import build_mlp
from flexflow_tpu.models.alexnet import build_alexnet
from flexflow_tpu.models.resnet import build_resnet50
from flexflow_tpu.models.bert import BertConfig, build_bert
from flexflow_tpu.models.llama import LlamaConfig, build_llama, llama_tp_strategy
from flexflow_tpu.models.mellum2 import Mellum2Config, build_mellum2
from flexflow_tpu.models.mixtral import MixtralConfig, build_mixtral
from flexflow_tpu.models.dlrm import build_dlrm
from flexflow_tpu.models.inception import build_inception_v3
from flexflow_tpu.models.resnext import build_resnext50
from flexflow_tpu.models.candle_uno import build_candle_uno
from flexflow_tpu.models.nmt import NMTConfig, build_nmt, nmt_dp_strategy
from flexflow_tpu.models.transformer import (
    TransformerConfig,
    build_transformer_encoder,
    build_transformer_encoder_decoder,
)
from flexflow_tpu.models.xdl import build_xdl

__all__ = [
    "build_mlp",
    "build_alexnet",
    "build_resnet50",
    "BertConfig",
    "build_bert",
    "LlamaConfig",
    "build_llama",
    "llama_tp_strategy",
    "Mellum2Config",
    "build_mellum2",
    "MixtralConfig",
    "build_mixtral",
    "build_dlrm",
    "build_inception_v3",
    "build_resnext50",
    "build_candle_uno",
    "NMTConfig",
    "build_nmt",
    "nmt_dp_strategy",
    "build_xdl",
    "TransformerConfig",
    "build_transformer_encoder",
    "build_transformer_encoder_decoder",
]
