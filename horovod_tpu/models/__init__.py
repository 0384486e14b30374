from horovod_tpu.models.resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152  # noqa: F401
from horovod_tpu.models.bert import BertConfig, BertModel, BertForPreTraining  # noqa: F401
from horovod_tpu.models.mlp import MLP  # noqa: F401
from horovod_tpu.models.gpt import (  # noqa: F401
    GPT, GPTConfig, GPTEmbed, GPTHead, GPTMoEBlock,
)
from horovod_tpu.models.vgg import VGG, VGG11, VGG13, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.vit import ViT, ViTConfig  # noqa: F401
from horovod_tpu.models.llama import Llama, LlamaBlock, LlamaConfig  # noqa: F401
from horovod_tpu.models.smallthinker import (  # noqa: F401
    SmallThinker, SmallThinkerBlock, SmallThinkerConfig,
)
from horovod_tpu.models.nemotron_h import (  # noqa: F401
    NemotronH, NemotronHBlock, NemotronHConfig,
)
from horovod_tpu.models.afmoe import (  # noqa: F401
    Afmoe, AfmoeBlock, AfmoeConfig,
)
from horovod_tpu.models.joyai_flash import (  # noqa: F401
    JoyAIFlash, JoyAIFlashBlock, JoyAIFlashConfig,
)
from horovod_tpu.models.kimi_linear import (  # noqa: F401
    KimiLinear, KimiLinearBlock, KimiLinearConfig,
)
from horovod_tpu.models.t5 import (  # noqa: F401
    T5, T5Config, t5_beam_decode, t5_generate, t5_greedy_decode,
)
from horovod_tpu.models.generate import (  # noqa: F401
    beam_search, generate, prefill_prefix,
)
from horovod_tpu.models.lora import (  # noqa: F401
    adapter_loss_fn, adapter_loss_fn_via_extra, lora_apply, lora_init,
    lora_merge, lora_wire_numbers,
)
from horovod_tpu.models.speculative import (  # noqa: F401
    speculative_accept, speculative_generate,
)
