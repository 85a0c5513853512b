"""The jax half of the retrace witness: transfer-guard arming + blessed D2H.

The registry (env check, warmup barrier, ``RetraceViolation``) lives in
``tpuserve.analysis.witness`` so the analysis package stays importable on
bare Python; this module is the part that needs jax. When the server
declares its warmup barrier under ``TPUSERVE_RETRACE_WITNESS=1``,
``arm_transfer_guard`` flips jax's device-to-host transfer guard to
"disallow": any *implicit* D2H readback — a stray ``.item()``, ``float()``
on a live array, ``np.asarray`` outside a blessed site — raises instead of
silently serializing the pipeline. Every deliberate readback on the
serving path routes through ``host_fetch`` (or an ``allow_transfers``
block), which is exactly the sanctioned-pattern contract the static pass
(TPS502) enforces on traced bodies, extended to runtime.

Host-to-device stays on jax's default: compiled calls take numpy batches
implicitly by design (the assembly arena hands host buffers straight to
dispatch), so guarding that direction would only bless every call site and
prove nothing.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from tpuserve.analysis import witness


def arm_transfer_guard() -> bool:
    """Disallow implicit device-to-host transfers for the rest of the
    process; no-op (returns False) when the retrace witness is off."""
    if not witness.retrace_enabled():
        return False
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    return True


def allow_transfers():
    """Context manager blessing explicit D2H inside the block — for the
    odd-shaped readbacks (``bool(np.asarray(out["done"]))``-style) that
    don't fit ``host_fetch``'s whole-tree signature."""
    return jax.transfer_guard_device_to_host("allow")


def host_fetch(tree: Any) -> Any:
    """THE blessed device->host readback: materialize every leaf as a
    numpy array under an explicit allow. All deliberate serving-path
    fetches (runtime.fetch, the engine's step/extract syncs, lifecycle
    canaries) funnel through here so the armed guard only ever trips on
    transfers nobody meant to make."""
    with jax.transfer_guard_device_to_host("allow"):
        return jax.tree_util.tree_map(np.asarray, tree)


def host_fetch_together(tree: Any) -> Any:
    """``host_fetch`` as ONE ``jax.device_get``: every leaf's copy to the
    host is started before the first is awaited, so the wait is the slowest
    leaf's and not their sum (the generation engine's out-block of five small
    arrays, ISSUE 41). Leaves already on the host pass through."""
    with jax.transfer_guard_device_to_host("allow"):
        return jax.device_get(tree)
