"""radixmesh_tpu — a TPU-native distributed radix prefix cache + serving stack.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of RadixMesh
(reference: /root/reference, see SURVEY.md): a distributed radix-tree prefix
cache whose KV blocks live as ``jax.Array`` pages in TPU HBM, replicated
across prefill/decode nodes via idempotent oplogs over a ring, with
master-free rank-based conflict resolution, distributed duplicate-KV GC, and
a cache-aware router — plus the model runtime the reference left as a seam:
paged-attention Pallas kernels, Llama-3/Qwen2 model families, a continuous
batching scheduler, and tp/dp/sp sharding over a ``jax.sharding.Mesh``.
"""

__version__ = "0.1.0"

from radixmesh_tpu.config import MeshConfig, NodeRole, load_config
from radixmesh_tpu.cache.radix_tree import RadixTree, TreeNode, MatchResult


def __getattr__(name: str):
    # PEP 562 lazy exports: kv_pool imports jax (~5 s cold), which the
    # pure cache/mesh/router surface never needs — fifty ring-node
    # processes on one core must not pay 50 jax imports.
    if name in ("PagedKVPool", "SlotAllocator"):
        from radixmesh_tpu.cache import kv_pool

        return getattr(kv_pool, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "MeshConfig",
    "NodeRole",
    "load_config",
    "RadixTree",
    "TreeNode",
    "MatchResult",
    "PagedKVPool",
    "SlotAllocator",
    "__version__",
]
