"""firedancer_tpu — a TPU-native framework with the capabilities of Firedancer.

Layer map (mirrors the reference's bottom-up layering, re-designed TPU-first):

  utils/     environment layer: config, logging, histograms, rng
  tango/     IPC messaging: mcache/dcache rings, flow control, tcache (C + py)
  ops/       protocol algorithms as batched JAX/Pallas kernels: ed25519,
             sha512/256, pack conflict prefilter
  tiles/     tile framework: run loop, topology, the pipeline stages
  parallel/  the multi-chip dry run (the verify tile's device pool on
             virtual CPU devices)
"""

__version__ = "0.1.0"
