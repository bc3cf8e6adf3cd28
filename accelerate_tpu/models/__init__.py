from .llama import LlamaConfig, create_llama, llama_apply, llama_loss, init_llama_params
from .bert import BertConfig, create_bert, bert_apply, bert_classification_loss, init_bert_params
from .gpt2 import GPT2Config, create_gpt2, gpt2_apply, gpt2_loss, init_gpt2_params
from .t5 import T5Config, create_t5, t5_apply, t5_loss, init_t5_params
from .resnet import ResNetConfig, create_resnet, resnet_apply, resnet_classification_loss
from .lfm2 import Lfm2Config, create_lfm2, lfm2_apply, lfm2_loss, init_lfm2_params
from .axk1 import AxK1Config, create_axk1, axk1_apply, axk1_loss, init_axk1_params
from .longcat import LongcatFlashConfig, create_longcat, longcat_apply, longcat_loss, init_longcat_params
from .family import ServingFamily
