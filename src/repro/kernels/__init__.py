"""repro.kernels — Pallas kernels for the repo's compute hot spots, each
with a pure-jnp oracle in `ref.py` and a dispatch wrapper in `ops.py`:
`csim` (the paper's Eq. 3 windowed L0-distance loop, O(n·range·d)),
`flash_attention`, `rmsnorm`, and stochastic `quantize` (the compression
ECD-PSGD gossips with).  Tests compare kernel vs oracle in interpret mode
and compile each kernel for a described TPU v5e.
"""
