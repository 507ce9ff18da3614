"""Blob object store: the substrate of Redwood's broadcast/fetch.

Redwood serializes ASTs/arguments to Azure Blob storage and passes
references; workers deserialize on their side. Here: pickled blobs (zstd)
on a shared filesystem root, addressed by content-hash keys — broadcast is
"put once, pass the BlobRef to every task"."""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
from typing import Any

try:
    import zstandard as zstd
except ImportError:  # pragma: no cover
    zstd = None


@dataclasses.dataclass(frozen=True)
class BlobRef:
    root: str
    key: str
    nbytes: int

    def fetch(self) -> Any:
        return ObjectStore(self.root).get(self)


class ObjectStore:
    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def put(self, obj: Any) -> BlobRef:
        raw = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        if zstd is not None:  # one (thread-unsafe) compressor per call
            raw = zstd.ZstdCompressor(level=3).compress(raw)
        key = hashlib.sha1(raw).hexdigest()[:24]
        path = os.path.join(self.root, key)
        if not os.path.exists(path):  # content-addressed: dedup free
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.rename(tmp, path)
        return BlobRef(self.root, key, len(raw))

    def get(self, ref: BlobRef) -> Any:
        with open(os.path.join(self.root, ref.key), "rb") as f:
            raw = f.read()
        if zstd is not None:
            raw = zstd.ZstdDecompressor().decompress(raw)
        return pickle.loads(raw)
