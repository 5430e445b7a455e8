"""Paged-KV decode attention kernel (block-table walk, live-block exit).

Three layers per the repo kernel convention:
    kernel.py — Pallas block-table walk with online softmax, fused KV
                scatter, and per-request live-block early exit
    ops.py    — ``use_pallas`` dispatch: the compiled kernel on a TPU,
                the reference (or the kernel in interpret mode) elsewhere
    ref.py    — live-length grouped-GQA jnp oracle + ``write_kv`` scatter
"""
