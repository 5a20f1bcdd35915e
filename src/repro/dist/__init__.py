"""Distribution substrate shared by the SSumM summarizer and the LM stack.

Three concerns, one vocabulary:

  * :mod:`repro.dist.sharding` — logical-axis → mesh-axis rule tables
    (``make_rules(mesh, mode)``) consumed by the lowering, dry-run, train,
    serve, and distributed-summarize paths, plus the supernode ownership
    hash the edge-sharded step routes with;
  * :mod:`repro.dist.compress` — int8 / top-k payload codecs with
    error-feedback buffers for the cross-pod gradient boundary;
  * :mod:`repro.dist.microbatch` — gradient accumulation that matches the
    full-batch gradient.
"""

from repro.dist.compress import (
    CompressConfig,
    compressed_allreduce,
    decode_int8,
    encode_int8,
    encode_topk,
    init_error_buffers,
    payload_bytes,
)
from repro.dist.microbatch import microbatch_grads
from repro.dist.sharding import MeshRules, make_mesh, make_rules, owner_hash_np

__all__ = [
    "CompressConfig",
    "MeshRules",
    "compressed_allreduce",
    "decode_int8",
    "encode_int8",
    "encode_topk",
    "init_error_buffers",
    "make_mesh",
    "make_rules",
    "microbatch_grads",
    "owner_hash_np",
    "payload_bytes",
]
