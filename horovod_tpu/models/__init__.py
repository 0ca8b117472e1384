import time as _time

_import_start = _time.time()

from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
                     ResNet152)
from .bert import (BertConfig, BertEncoder, BertForMaskedLM,
                   bert_base_config, bert_large_config, bert_tiny_config,
                   mlm_loss)
from .layers import chunked_lm_loss
from .gpt import (GPTConfig, GPTLMHeadModel, gpt2_medium_config,
                  gpt2_small_config, gpt_tiny_config, lm_loss)
from .granite import (GraniteConfig, GraniteLMHeadModel,
                      granite_tiny_config)
from .lfm2 import LFM2Config, LFM2LMHeadModel, lfm2_tiny_config
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3LMHeadModel,
                          deepseek_v3_tiny_config)
from .afmoe import AfmoeConfig, AfmoeLMHeadModel, afmoe_tiny_config
from .keye_vl import KeyeVLConfig, KeyeVLLMHeadModel, keye_vl_tiny_config
from .mnist import MnistCNN, MnistMLP, cross_entropy_loss
from .dlrm import (DLRMConfig, DLRMDense, bce_logits_loss,
                   dlrm_tiny_config, synthetic_click_batch)

__all__ = [
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101",
    "ResNet152",
    "BertConfig", "BertEncoder", "BertForMaskedLM", "bert_base_config",
    "bert_large_config", "bert_tiny_config", "mlm_loss",
    "GPTConfig", "GPTLMHeadModel", "gpt2_small_config",
    "gpt2_medium_config", "gpt_tiny_config", "lm_loss", "chunked_lm_loss",
    "GraniteConfig", "GraniteLMHeadModel", "granite_tiny_config",
    "LFM2Config", "LFM2LMHeadModel", "lfm2_tiny_config",
    "DeepseekV3Config", "DeepseekV3LMHeadModel", "deepseek_v3_tiny_config",
    "AfmoeConfig", "AfmoeLMHeadModel", "afmoe_tiny_config",
    "KeyeVLConfig", "KeyeVLLMHeadModel", "keye_vl_tiny_config",
    "MnistCNN", "MnistMLP", "cross_entropy_loss",
    "DLRMConfig", "DLRMDense", "bce_logits_loss", "dlrm_tiny_config",
    "synthetic_click_batch",
]

from ..common import timeline as _timeline

# hvd/import: the first to the last line of this file (flax and the
# kernels the models call come with it), as the package records its own.
_timeline.record("import", _import_start, _time.time(), module=__name__)
del _time, _timeline, _import_start
