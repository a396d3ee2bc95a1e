"""dds_tpu — a TPU-native Dependable Data Storage framework.

A from-scratch re-design of the capabilities of
``fmiguelgodinho/dependable-data-storage-csd2017`` (a Byzantine fault-tolerant,
replicated, CryptDB-style encrypted key->set store) built TPU-first:

- tier 0: batched big-integer limb arithmetic + Montgomery modmul/modexp as
  JAX/Pallas kernels (``dds_tpu.ops``)
- tier 1: homomorphic / property-preserving encryption schemes
  (``dds_tpu.models``) with pluggable cpu / tpu backends
- tier 2: asyncio BFT-ABD replicated core (``dds_tpu.core``)
- tier 3: REST proxy / encrypted query engine (``dds_tpu.http``)
- tier 4: supervisor control plane (``dds_tpu.core.supervisor``)
- tier 5: workload harness, bench client, fault injector
  (``dds_tpu.clt``, ``dds_tpu.malicious``)

The reference system is Scala/Akka; nothing here is a translation — the
compute-heavy homomorphic arithmetic is re-designed as fixed-shape batched
limb tensors for the TPU VPU/MXU, and the replication control plane is
asyncio + HMAC-framed transports.
"""

__version__ = "0.1.0"


def _place_jax_compilation_cache() -> None:
    """Give JAX's persistent compilation cache a fixed place.

    The tier-0 kernels compile one executable per (modulus limb count,
    batch shape), and a cold proxy or client process otherwise recompiles
    every shape it serves. A cache that moves between runs never hits, so
    the path is fixed: `JAX_COMPILATION_CACHE_DIR`, when the operator set
    it, is left alone and nothing else is set in code; otherwise the cache
    lives in `.jax_cache/` at the root of this checkout. It is an
    environment variable, which jax reads at ITS import: import dds_tpu
    before jax (no jax import cost here for host-only consumers).
    """
    import os

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")


_place_jax_compilation_cache()
