"""The dense GQA decoder: per layer RMSNorm -> grouped-query attention
with rotary position over the whole causal context -> RMSNorm -> SwiGLU
MLP, as ``granite-3-2b`` and ``glm4-9b-20l`` are served.  The module a
configuration gets where its file names no ``bench_arch``.

What an architecture module gives the harness, each a plain function of
the configuration (``spec``) or its ``model`` block:

- ``program_config(spec)``: the program's ``ModelConfig`` for the file;
- ``leaf_specs(model)``, ``make_params(model, seed)``: the weight tree in
  the engine's layout, made on the device from the seed;
- ``served_gaps(model, params, prompt, served, low=None, pad_to=0)``: the
  float32 reference's gaps (``reference.py``; it imports nothing of the
  program);
- ``step_flops(model, segments, logits_rows)``: the model FLOPs of one
  tick (``model_step_mfu``);
- ``KERNELS``: each kernel's name -> the HLO names of its custom calls,
  which the trace reduction times;
- ``kernel_cost(model, segments)``: each kernel's (FLOPs, bytes) in one
  tick, which its ``<kernel>_roofline`` reader reads.
"""
import dataclasses

from bench.flops import paged_kernel_cost, step_flops  # noqa: F401
from bench.reference import served_gaps  # noqa: F401
from bench.weights import leaf_specs, make_params  # noqa: F401

KERNELS = {"paged_attention": ("paged_attention_update_pallas",
                               "paged_attention_pallas")}

# the ``model`` keys the program's registered ``arch`` takes from the file
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "act", "norm", "rope_theta",
                "rope_fraction", "tie_embeddings", "dtype")


def program_config(spec: dict):
    """The program's ModelConfig: its registered ``arch`` at the file's
    sizes."""
    from repro.config import get_config
    model = spec["model"]
    return dataclasses.replace(get_config(spec["arch"]),
                               **{k: model[k] for k in PROGRAM_KEYS})


def kernel_cost(model: dict, segments) -> dict:
    return {"paged_attention": paged_kernel_cost(model, segments)}
