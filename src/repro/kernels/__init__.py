"""Pallas TPU kernels for the hot loops.

Each kernel is a subpackage with:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, GQA reshapes, interpret flag)
  ref.py    — pure-jnp oracle used by the test sweeps

Kernels:
  band_reclassify  — the served incremental step: stream the union of the
                     k views' water-band windows HBM→VMEM once and relabel
                     each view's window in place (paper's core saving);
                     `core/sharded.py`'s update step launches it
  eps_affine       — eps = F·w − b fused with labeling + per-tile positive
                     counts; no caller outside its tests
  flash_attention  — causal GQA flash attention forward; no caller outside
                     its tests
  decode_attention — single-token GQA attention over a long KV cache; no
                     caller outside its tests
  wkv6             — RWKV-6 chunked WKV recurrence; no caller outside its
                     tests
"""
