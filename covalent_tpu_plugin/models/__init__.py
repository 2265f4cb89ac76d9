"""Model zoo for the BASELINE configs.

The reference ships no models (its ML coverage is one sklearn SVM inside a
functional test, ``tests/functional_tests/svm_workflow.py``); these are the
electron payloads the TPU north star names: the MNIST CNN for the
data-parallel v5e-8 config and a GPT-style 125M LM for the multi-host
pretrain config, both written mesh-first so the same code spans one chip to
a pod.
"""

from ..obs.jitstats import watch_jit
from .beam import beam_search
from .data import synthetic_lm_batch, synthetic_lm_batches
from .decode import generate, inference_params, init_cache
from .moe import MoEMlp, lm_loss_with_moe_aux
from .pipeline_lm import pipeline_lm_forward, pipeline_lm_loss
from .lora import (
    LoRATrainState,
    add_lora,
    lora_mask,
    lora_optimizer,
    lora_train_params,
    make_lora_train_state,
    make_lora_train_step,
    merge_lora,
    quantize_then_lora,
)
from .quant import QuantDenseGeneral, quantize_lm
from .serve import continuous_generate
from .speculative import speculative_generate, speculative_sample
from .mlp import MLP, MnistCNN, synthetic_mnist
from .transformer import TransformerConfig, TransformerLM, lm_125m_config
from .train import (
    classifier_loss,
    cross_entropy_loss,
    lm_loss,
    make_classifier_train_step,
    make_lm_train_step,
    make_sharded_train_state,
    make_train_step,
)

# Every module above imports jax: from here on the process keeps its own
# account of its compiles (seconds by phase, persistent-cache hits and
# misses) in the metrics registry.
watch_jit()

__all__ = [
    "MLP",
    "MnistCNN",
    "synthetic_mnist",
    "synthetic_lm_batch",
    "synthetic_lm_batches",
    "beam_search",
    "generate",
    "continuous_generate",
    "inference_params",
    "init_cache",
    "MoEMlp",
    "lm_loss_with_moe_aux",
    "pipeline_lm_forward",
    "pipeline_lm_loss",
    "QuantDenseGeneral",
    "quantize_lm",
    "speculative_generate",
    "speculative_sample",
    "LoRATrainState",
    "add_lora",
    "lora_mask",
    "lora_optimizer",
    "lora_train_params",
    "make_lora_train_state",
    "make_lora_train_step",
    "merge_lora",
    "quantize_then_lora",
    "TransformerConfig",
    "TransformerLM",
    "lm_125m_config",
    "cross_entropy_loss",
    "classifier_loss",
    "lm_loss",
    "make_sharded_train_state",
    "make_train_step",
    "make_lm_train_step",
    "make_classifier_train_step",
]
