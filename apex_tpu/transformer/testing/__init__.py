"""Test/benchmark harness (reference ``apex/transformer/testing/``)."""
from .commons import (  # noqa: F401
    TEST_SUCCESS_MESSAGE,
    initialize_distributed,
    print_separator,
    set_random_seed,
)
from .distributed_test_base import (  # noqa: F401
    DistributedTestBase,
    NcclDistributedTestBase,
    UccDistributedTestBase,
)
from .standalone_transformer_lm import (  # noqa: F401
    GPTConfig,
    LatentKV,
    LayerKind,
    bert_forward,
    gpt_embed,
    gpt_forward,
    gpt_loss,
    gpt_partition_specs,
    init_gpt_fp8_carriers,
    init_gpt_fp8_states,
    init_gpt_params,
    record_gpt_grad_amaxes,
    transformer_block,
)
from .standalone_gpt import gpt_model_provider  # noqa: F401
from .standalone_bert import bert_model_provider  # noqa: F401
