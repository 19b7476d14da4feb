"""Fault-tolerant checkpointing: atomic, checksummed, async, resumable.

Format: one directory per step containing
  * ``manifest.msgpack`` — path → (shape, dtype, crc32, byte offset/len)
  * ``shard_<i>.bin.zst`` — zstd-compressed concatenated leaf buffers

Safety properties:
  * atomic publish: written to ``<step>.tmp`` then os.rename'd — a crash
    mid-write never corrupts the latest checkpoint;
  * integrity: per-leaf crc32 verified on restore (bit-rot detection);
  * async: ``AsyncCheckpointer`` snapshots to host memory synchronously
    (cheap) and serializes on a background thread, so the train loop
    stalls only for the device→host copy;
  * bounded retention: keep_last garbage collection;
  * exact resume: restore returns (tree, step); the stateless data
    pipeline (data/tokens.py) replays from any step bit-identically.

On a real multi-host pod each host writes only its addressable shards
(jax.experimental.multihost_utils); this single-host implementation
gathers — the format and protocol are host-count agnostic.
"""

from __future__ import annotations

import os
import shutil
import threading
import zlib
from pathlib import Path

import jax
import msgpack
import numpy as np
import zstandard

from repro.utils.trees import flatten_dict, unflatten_dict

_MANIFEST = "manifest.msgpack"
_SHARD = "shard_0.bin.zst"


def _shard_decompress(data: bytes) -> bytes:
    """Decompress a stream-written shard.  A frame written through
    ``stream_writer`` carries no content size, so decompress it as a
    stream rather than sizing an output buffer up front."""
    return zstandard.ZstdDecompressor().decompressobj().decompress(data)


def save(ckpt_dir: str | Path, step: int, tree, keep_last: int = 3) -> Path:
    """Synchronous checkpoint write. Returns the published directory."""
    ckpt_dir = Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    final = ckpt_dir / f"step_{step:010d}"
    tmp = ckpt_dir / f"step_{step:010d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    flat = flatten_dict(tree)
    manifest = {"step": step, "codec": "zstd", "leaves": {}}
    offset = 0
    with open(tmp / _SHARD, "wb") as f:
        writer = zstandard.ZstdCompressor(level=3).stream_writer(f)
        for path, leaf in sorted(flat.items()):
            arr = np.asarray(leaf)
            buf = arr.tobytes()
            manifest["leaves"][path] = {
                "shape": list(arr.shape),
                "dtype": arr.dtype.str,
                "crc32": zlib.crc32(buf),
                "offset": offset,
                "nbytes": len(buf),
            }
            writer.write(buf)
            offset += len(buf)
        writer.close()
    (tmp / _MANIFEST).write_bytes(msgpack.packb(manifest))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)            # atomic publish
    _gc(ckpt_dir, keep_last)
    return final


def _gc(ckpt_dir: Path, keep_last: int) -> None:
    steps = sorted(p for p in ckpt_dir.iterdir()
                   if p.is_dir() and p.name.startswith("step_")
                   and not p.name.endswith(".tmp"))
    for p in steps[:-keep_last]:
        shutil.rmtree(p)


def latest_step(ckpt_dir: str | Path) -> int | None:
    ckpt_dir = Path(ckpt_dir)
    if not ckpt_dir.exists():
        return None
    steps = [int(p.name.split("_")[1]) for p in ckpt_dir.iterdir()
             if p.is_dir() and p.name.startswith("step_")
             and not p.name.endswith(".tmp")]
    return max(steps) if steps else None


def restore(ckpt_dir: str | Path, step: int | None = None):
    """Returns (tree, step). Verifies per-leaf crc32."""
    ckpt_dir = Path(ckpt_dir)
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    d = ckpt_dir / f"step_{step:010d}"
    manifest = msgpack.unpackb((d / _MANIFEST).read_bytes())
    raw = _shard_decompress((d / _SHARD).read_bytes())
    flat = {}
    for path, meta in manifest["leaves"].items():
        buf = raw[meta["offset"]:meta["offset"] + meta["nbytes"]]
        if zlib.crc32(buf) != meta["crc32"]:
            raise IOError(f"checksum mismatch for {path} in {d}")
        flat[path] = np.frombuffer(buf, dtype=np.dtype(meta["dtype"])).reshape(
            meta["shape"]).copy()
    return unflatten_dict(flat), manifest["step"]


class AsyncCheckpointer:
    """Overlap serialization with training.

    ``submit`` synchronously snapshots device arrays to host numpy
    (the only part that must see a consistent state), then hands the
    write to a daemon thread.  ``wait()`` joins the in-flight write
    (call before exit / before restoring).
    """

    def __init__(self, ckpt_dir: str | Path, keep_last: int = 3):
        self.ckpt_dir = Path(ckpt_dir)
        self.keep_last = keep_last
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def submit(self, step: int, tree) -> None:
        self.wait()
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)

        def work():
            try:
                save(self.ckpt_dir, step, host_tree, self.keep_last)
            except BaseException as e:  # noqa: BLE001
                self.error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            err, self.error = self.error, None
            raise err
