"""apex_tpu.models — model zoo for examples and benchmarks."""

from .resnet import (ResNet, BasicBlock, Bottleneck, resnet18, resnet34,
                     resnet50, resnet101, resnet152, stem_weight_to_s2d,
                     convert_stem_to_s2d)
from .bert import (BertConfig, BertModel, BertForPretraining, bert_base,
                   bert_large)
from .dcgan import Generator, Discriminator, dcgan
from .gpt import GPTConfig, GPT, gpt2_small, gpt2_medium
from .llama import LlamaConfig, Llama, RMSNorm, llama_params_to_tp
from .mixtral import MixtralConfig, Mixtral
from .laguna import LagunaConfig, Laguna
from .nemotron_h import NemotronHConfig, NemotronH
from .deepseek_v3 import DeepseekV3Config, DeepseekV3
from .speculative import generate_speculative
from .beam import beam_search
from .t5 import T5Config, T5
