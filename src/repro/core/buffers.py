"""Buffer objects with per-server validity and dynamic content size.

``content_size_buffer`` implements the paper's ``cl_pocl_content_size``
extension (§5.3): a designated 4-byte buffer holds the number of
meaningful bytes; migrations and reads move only that prefix, and a
kernel that writes both the buffer and its size buffer leaves only the
prefix in the buffer. The canonical array lives host-side in the
simulation (all copies are bit-identical); what the runtime tracks is
*where* valid copies exist and what moving them costs.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

import numpy as np

_buf_ids = itertools.count(1)


@dataclasses.dataclass
class Buffer:
    nbytes: int
    content_size_buffer: Optional["Buffer"] = None
    name: str = ""
    id: int = dataclasses.field(default_factory=lambda: next(_buf_ids))
    # canonical contents: the whole array, or, where the kernel that
    # wrote it also wrote its content-size buffer, only the leading rows
    # that hold the used prefix (``transfer_bytes()``, rounded up to a
    # whole row)
    data: Optional[np.ndarray] = None
    valid_on: set = dataclasses.field(default_factory=set)  # server names
    registered_mr: set = dataclasses.field(default_factory=set)
    # content generation: bumped on every write/clobber. The runtime's
    # in-flight migration table snapshots it to detect transfers whose
    # payload went stale mid-flight (DESIGN.md §3): a coalesce hit or an
    # arrival-side validity update is only honored when the version still
    # matches the snapshot.
    version: int = 0
    # content-addressed store attachment (DESIGN.md §5): digest of the
    # content this buffer shares through the cluster's BufferStore, or
    # None when private. Managed by the store (attach/detach/cow_fork);
    # a write always forks the buffer back to private first.
    store_key: Optional[bytes] = None

    def transfer_bytes(self) -> float:
        """Bytes a migration must move (content-size aware). Clamped to
        ``[0, nbytes]``: a corrupt or stale ``cl_pocl_content_size``
        value must never produce a negative or over-long transfer."""
        if self.content_size_buffer is not None \
                and self.content_size_buffer.data is not None:
            used = int(np.asarray(
                self.content_size_buffer.data).reshape(-1)[0])
            return float(min(max(used, 0), self.nbytes))
        return float(self.nbytes)

    def set_data(self, arr, on: str):
        self.data = arr
        self.valid_on = {on}
        self.version += 1

    def invalidate_except(self, server: str):
        self.valid_on = {server}
        self.version += 1
