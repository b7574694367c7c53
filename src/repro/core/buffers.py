"""Buffer objects with per-server validity and dynamic content size.

``content_size_buffer`` implements the paper's ``cl_pocl_content_size``
extension (§5.3): a designated 4-byte buffer holds the number of
meaningful bytes; migrations and reads move only that prefix, and a
kernel that writes both the buffer and its size buffer leaves only the
prefix in the buffer. One canonical array stands for all of a buffer's
copies (they are bit-identical): a host array, or the ``jax.Array`` that
a kernel left on its device, which stays there until something needs
host bytes (``Buffer.host()``). What the runtime tracks is *where* valid
copies exist and what moving them costs.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

import jax
import numpy as np

_buf_ids = itertools.count(1)


def _used_rows(host: np.ndarray, nbytes: float) -> np.ndarray:
    """The leading rows of ``host`` that hold its first ``nbytes`` bytes
    (a part row counts as a row)."""
    if host.nbytes == 0:
        return host
    return host[:-(-int(nbytes) * host.shape[0] // host.nbytes)]


@dataclasses.dataclass
class Buffer:
    nbytes: int
    content_size_buffer: Optional["Buffer"] = None
    name: str = ""
    id: int = dataclasses.field(default_factory=lambda: next(_buf_ids))
    # canonical contents: a host array or a kernel's ``jax.Array``; see
    # ``cut`` for a content-sized kernel output
    data: Optional[np.ndarray | jax.Array] = None
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
    # the kernel that wrote ``data`` also wrote its content-size buffer:
    # ``host()`` keeps only the leading rows that hold the used prefix
    # (``transfer_bytes()``, rounded up to a whole row)
    cut: bool = False

    @property
    def on_device(self) -> bool:
        return isinstance(self.data, jax.Array)

    def transfer_bytes(self) -> float:
        """Bytes a migration must move (content-size aware). Clamped to
        ``[0, nbytes]``: a corrupt or stale ``cl_pocl_content_size``
        value must never produce a negative or over-long transfer. A size
        buffer still on its device is made host bytes here
        (``host()``): the host waits for the kernel that wrote it."""
        csb = self.content_size_buffer
        if csb is not None and csb.data is not None:
            used = int(np.asarray(csb.host()).reshape(-1)[0])
            return float(min(max(used, 0), self.nbytes))
        return float(self.nbytes)

    def host(self):
        """The contents as a host array (``None`` while there are none);
        see ``to_host()``."""
        self.to_host()
        return self.data

    def to_host(self) -> int:
        """Make the contents host bytes, the content-size buffer's first,
        and return the bytes this copied off a device. A device array is
        copied off its device once, whole, and the host array takes its
        place, cut as ``cut`` says. Neither ``version`` nor ``valid_on``
        changes: the content is the same, only its representation is
        not."""
        if not (self.cut or self.on_device):
            return 0
        csb = self.content_size_buffer
        copied = csb.to_host() if csb is not None else 0
        if self.on_device:
            copied += self.data.nbytes
        arr = np.asarray(self.data)
        if self.cut:
            arr = _used_rows(arr, self.transfer_bytes())
            self.cut = False
        self.data = arr
        return copied

    def set_data(self, arr, on: str, cut: bool = False):
        self.data = arr
        self.cut = cut
        self.valid_on = {on}
        self.version += 1

    def invalidate_except(self, server: str):
        self.valid_on = {server}
        self.version += 1
