"""Model zoo: the reference's five workload models + a long-context decoder
LM, TPU-first flax modules."""

from .generate import decode_step, generate, prefill  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTLM,
    gpt_layout,
    gpt_medium,
    gpt_small,
    gpt_tiny,
    lm_eval,
    lm_loss,
    nan_taps,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig,
    afmoe_tiny,
    trinity_large_ep8,
)
from .joyai import (  # noqa: F401
    JoyaiConfig,
    glm5_ep16,
    glm5_tiny,
    joyai_llm_flash,
    joyai_tiny,
)
from .jamba import (  # noqa: F401
    JambaConfig,
    jamba2_3b,
    jamba_tiny,
)
from .mimo import (  # noqa: F401
    MimoConfig,
    mimo_tiny,
    mimo_v25_ep16,
)
from .lfm2 import (  # noqa: F401
    Lfm2Config,
    lfm2_24b_a2b,
    lfm2_tiny,
)
from .evabyte import (  # noqa: F401
    EvaByteConfig,
    evabyte_6_5b,
    evabyte_tiny,
)
from .ling import (  # noqa: F401
    LingConfig,
    ling3_flash_ep8,
    ling_tiny,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    nemotron3_super_ep4,
    nemotron_h_tiny,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    qwen3_next_ep4,
    qwen3_next_tiny,
)
from .ouro import (  # noqa: F401
    OuroConfig,
    ouro_2_6b,
    ouro_tiny,
)
from .lenet import LeNet5  # noqa: F401
from .resnet import (  # noqa: F401
    CifarResNet,
    ImageNetResNet,
    ResNet20,
    ResNet50,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertEncoder,
    BertForMLM,
    bert_base,
    bert_layout,
    bert_tiny,
    max_predictions_for,
    mlm_eval,
    mlm_loss,
)
from .vit import (  # noqa: F401
    ViT,
    ViTConfig,
    vit_layout,
    vit_s16,
    vit_tiny,
)
from .seq2seq import (  # noqa: F401
    Seq2SeqConfig,
    Seq2SeqLM,
    seq2seq_eval,
    seq2seq_generate,
    seq2seq_layout,
    seq2seq_loss,
    seq2seq_small,
    seq2seq_tiny,
)
from .widedeep import (  # noqa: F401
    WideDeep,
    WideDeepConfig,
    widedeep_layout,
    widedeep_eval,
    widedeep_loss,
    widedeep_test_config,
)


def make_nan_taps(model):
    """Best-effort NaN-provenance tap forward for ``obs.dynamics``:
    ``tap_fn(params, batch) -> {"NNN_module": nonfinite_count}`` with
    the forward position encoded in the key (``000_wte``, ``001_h0``,
    ... — jit canonicalizes dict outputs to sorted key order, so bare
    module names would lose forward order), or None for models without
    activation taps (provenance then falls back to the model-agnostic
    parameter/gradient censuses)."""
    if isinstance(model, GPTLM):
        return nan_taps(model)
    return None
