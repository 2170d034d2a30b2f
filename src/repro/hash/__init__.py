"""repro.hash -- the public hashing engine: `HashSpec` + `Hasher`.

The paper's families as a keyed *object* (CLHASH's shape: scheme + key
material), jit-native end to end:

    spec = HashSpec(family="multilinear", n_hashes=4, out_bits=64)
    hasher = Hasher.from_spec(spec, max_len=128)
    h = jax.jit(lambda hs, t: hs(t))(hasher, tokens)   # pure JAX, (B, K, 2)
    hb = hasher.hash_batch(ragged_items)               # host batch, 1 launch

Submodules: spec (HashSpec), hasher (Hasher/HashPlan), keyring (bounded-LRU
deterministic defaults), streaming (two-level incremental fingerprints),
sharding (Lemire-reduced shard routing), tree (mesh-parallel HalftimeHash-
style tree fingerprints for long inputs). The legacy `core.ops` free
functions remain as bit-identical deprecation shims over this package.
"""
from . import distributed, faults, keyring, service, sharding, streaming, tree  # noqa: F401
from .distributed import (  # noqa: F401
    DeviceShardedBloom, FilterShardBackend, ShardedHasher,
    bloom_shard_backends)
from .faults import FaultEvent, FaultPlan, FaultyTransport  # noqa: F401
from .hasher import Hasher, HashPlan, default_plan  # noqa: F401
from .service import (  # noqa: F401
    AdmissionService, BreakerConfig, CircuitBreaker, InProcessTransport,
    RetryPolicy, ShardReply, ShardRequest, VirtualClock)
from .sharding import reduce_range, shard_assignment  # noqa: F401
from .spec import DEFAULT_SEED, FAMILY_NAMES, HashSpec  # noqa: F401
from .streaming import StreamState, fingerprint_bytes, stream_digest_host  # noqa: F401
from .tree import (  # noqa: F401
    PytreeFingerprint, TreeHasher, TreeSpec, TreeStream, default_tree_hasher,
    fingerprint_pytree, root_of_leaf_fingerprints, stream_tree)
