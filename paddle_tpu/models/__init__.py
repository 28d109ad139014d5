"""Model zoo: flagship recipes exercising the framework end-to-end.

Counterpart of the reference's flagship integration models
(``test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py`` and
the out-of-repo PaddleNLP model zoo referenced by BASELINE configs).
"""

from . import llama  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_tiny_config,
    llama3_8b_config,
    llama3_70b_config,
)
from . import ssd  # noqa: F401
from .ssd import (  # noqa: F401
    SSDConfig,
    SSDForCausalLM,
    SSDModel,
    ssd_tiny_config,
    ssd_tiny_hybrid_config,
    ssd_8b_config,
)
from . import ernie  # noqa: F401
from . import hf_compat  # noqa: F401
from . import ocr  # noqa: F401
from .hf_compat import (  # noqa: F401
    ernie_config_from_transformers,
    ernie_from_transformers,
    llama_config_from_transformers,
    llama_from_transformers,
    llama_to_transformers_state_dict,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForSequenceClassification,
    ErnieModel,
    ernie_tiny_config,
)
from .mla_moe import (  # noqa: F401
    MlaMoeConfig,
    MlaMoeForCausalLM,
    mla_moe_tiny_config,
)
from .swa_moe import (  # noqa: F401
    SwaMoeConfig,
    SwaMoeForCausalLM,
    swa_moe_tiny_config,
)
