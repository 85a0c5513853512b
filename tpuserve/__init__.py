"""tpuserve — a TPU-native HTTP inference-serving framework.

A ground-up rebuild of the capabilities of ``zyin3/tensorflow_web_deploy``
(a TensorFlow-GPU web inference server: Flask/WSGI predict handler, request
batching, host-side image preprocessing, SavedModel-backed models) designed
idiomatically for JAX/XLA on TPU:

- asyncio HTTP layer (``tpuserve.server``) feeding
- a static-shape batching engine (``tpuserve.batcher``: padded batches,
  bucketed sequence lengths, deadline flush, dispatch pipelining) that runs
- AOT-compiled XLA executables (``tpuserve.runtime``) over a
- ``jax.sharding.Mesh`` (``tpuserve.parallel``: data-parallel sharded-batch,
  replica groups, tensor-parallel partition rules; ``tpuserve.ops`` holds the
  Pallas kernels), with
- on-device resize/normalize preprocessing (``tpuserve.preproc``),
- TF SavedModel weight import with parity checks (``tpuserve.savedmodel``),
- first-class observability (``tpuserve.obs``).

The reference project could not be read in the build environment (see
SURVEY.md §0 — the mount was empty); the capability surface implemented here
is the one recorded in SURVEY.md §2, derived from driver-authored metadata.
"""

__version__ = "0.1.0"
