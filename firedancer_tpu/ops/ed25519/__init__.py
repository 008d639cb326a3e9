"""Ed25519 verification: the batched device paths (verify.py, the Pallas
kernels) and the strict host path (hostpath.py, golden.py)."""

import os as _os

#: lanes per grid step of pallas_kernel.verify_core; tunable via env for
#: experiments.  Kept here, where no JAX is imported, because the verify
#: tile counts the lanes its batches cost the kernel (`kernel_lanes`) in
#: processes that never load a backend.
TILE = int(_os.environ.get("FDT_PALLAS_TILE", "256"))
