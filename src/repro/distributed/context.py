"""Ambient SPMD context for model-internal distribution decisions.

Model code is mesh-agnostic; launchers activate an ``SPMDContext`` while
tracing so specific layers can opt into mesh-aware execution:

* ``apply_moe`` switches its gmm dispatch to a ``shard_map`` (per-device
  sort/scatter + tensor-parallel psum) — XLA SPMD cannot partition a
  global sort/scatter and otherwise replicates the full token stream
  (measured: 172 GB/device for one OLMoE layer at train_4k).
* ``_scan_groups`` stores its inter-group carries sequence-sharded over
  the tensor axis (Megatron-style sequence parallelism) so deep models'
  scan carries stay within HBM.

The CPU serving engine and the smoke tests never activate a context and
use the plain local paths.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Tuple

from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class SPMDContext:
    mesh: Mesh
    dp_axes: Tuple[str, ...]      # batch/token-parallel axes ("pod","data")
    tp_axis: str = "model"
    shard_activations: bool = True   # sequence-shard scan carries
    fsdp: bool = False               # weights d-dim sharded over dp_axes
    batch_axes: Tuple[str, ...] = ()  # decode-batch axes (seqpar kernels)

    @property
    def dp_size(self) -> int:
        return int(
            __import__("numpy").prod([self.mesh.shape[a]
                                      for a in self.dp_axes]))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp_axis])


_CTX: contextvars.ContextVar[Optional[SPMDContext]] = \
    contextvars.ContextVar("repro_spmd", default=None)


@contextlib.contextmanager
def spmd_context(ctx: SPMDContext):
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def current_spmd() -> Optional[SPMDContext]:
    return _CTX.get()


def spmd_for_mesh(mesh: Mesh, **kw) -> SPMDContext:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return SPMDContext(mesh=mesh, dp_axes=dp, **kw)
