"""Tiered KV block storage: the spill tiers under the HBM pool.

HBM is the scarcest resource in the stack; the paged pool
(:class:`~mxnet_tpu.serving.llm.LLMEngine`) used to FREE a refcount-0
prefix-cache block on LRU eviction, re-prefilling it from scratch when
the session returned. With a :class:`KVSpillTier` armed, eviction
instead *demotes* the block's content down a hierarchy indexed by the
same :mod:`~mxnet_tpu.serving.kv_hash` chain hashes the prefix cache
keys on:

- **tier 2 — pinned host RAM**: an LRU dict of exact block payloads
  (the raw pool rows, including the int8 bitcast-scale layout — byte
  identity is the token-identity guarantee), bounded by
  ``MXNET_TPU_LLM_KV_SPILL_BYTES``;
- **tier 3 — content-addressed disk** (optional,
  ``MXNET_TPU_LLM_KV_SPILL_DIR``): host-tier overflow demotes to
  :func:`mxnet_tpu.io.cache.blob_put` blobs, one file per chain hash,
  shareable across engines on one machine;
- **tier 4 — a remote peer** (optional,
  ``MXNET_TPU_LLM_KV_SPILL_PEERS``): fetch over the PR-17 block
  transport plane (:class:`~mxnet_tpu.io.transport.BlockClient`) from
  the :class:`~mxnet_tpu.io.transport.BlockServer` another engine
  exposes (``kv_spill_serve=True``) — the multi-turn session
  that returns to a *different* replica re-attaches instead of
  re-prefilling.

A later admission whose prefix misses HBM probes ``get()`` tier by
tier; a hit re-attaches by ``device_put``/DMA (the engine writes the
rows back into freshly allocated pool blocks) — prefill compute is
skipped entirely.

Locking discipline (tpulint C002): the internal lock guards ONLY the
host-tier dict. Disk IO, serialization and every socket fetch run
outside it, so a slow disk or a dead peer can never wedge a concurrent
``put``. Remote fetches are deadline-bounded and *contained*: any
transport fault (CRC-rejected garbled frame, retries exhausted, dead
endpoint) counts ``remote_errors`` and returns a miss — the engine
falls back to a local re-prefill, never hangs and never fails the
request.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as onp

from ..base import env_float
from .kv_codec import decode_blocks, encode_blocks, payload_nbytes
from .kv_hash import hash_hex

__all__ = ["KVSpillTier", "spill_bytes_default", "spill_dir_from_env",
           "spill_peers_from_env"]


def spill_bytes_default() -> int:
    """``MXNET_TPU_LLM_KV_SPILL_BYTES`` (default 256 MiB of host RAM)."""
    return int(env_float("MXNET_TPU_LLM_KV_SPILL_BYTES",
                         256 * 1024 * 1024))


def spill_dir_from_env() -> Optional[str]:
    """``MXNET_TPU_LLM_KV_SPILL_DIR`` — arms the content-addressed disk
    tier (empty/unset = host RAM only)."""
    return os.environ.get("MXNET_TPU_LLM_KV_SPILL_DIR") or None


def spill_peers_from_env() -> List[str]:
    """``MXNET_TPU_LLM_KV_SPILL_PEERS`` — comma-separated
    ``host:port`` endpoints of peer engines' spill BlockServers."""
    raw = os.environ.get("MXNET_TPU_LLM_KV_SPILL_PEERS", "")
    return [p.strip() for p in raw.split(",") if p.strip()]


# the (de)serialization lives in kv_codec — ONE wire format shared with
# the prefill→decode handoff, so spill blobs and handoff frames can
# never drift apart (kv_codec module docstring has the layout contract)
_pack = encode_blocks
_unpack = decode_blocks
_nbytes = payload_nbytes


class KVSpillTier:
    """The host-RAM / disk / remote KV hierarchy under one engine's
    pool (see module docstring). Payloads are dicts of exact pool-row
    arrays keyed ``k``/``v`` (+ ``dk``/``dv`` when speculative decoding
    arms draft pools), indexed by the prefix cache's chain hash.

    ``serve=True`` exposes this tier's contents (host + disk) over a
    :class:`~mxnet_tpu.io.transport.BlockServer` under names
    ``kv/<hash hex>``; ``peers`` wires a pooled
    :class:`~mxnet_tpu.io.transport.BlockClient` that ``get()`` probes
    as the last tier. The tier is content-addressed, so it survives an
    engine pool rebuild (a fault reset clears pool *block ids*, not the
    spilled *content*)."""

    def __init__(self, *, bytes_limit: Optional[int] = None,
                 root: Optional[str] = None,
                 peers: Optional[List[str]] = None,
                 serve: bool = False, host: str = "127.0.0.1",
                 remote_deadline_s: float = 0.5,
                 name: str = "kv"):
        self.bytes_limit = int(bytes_limit if bytes_limit is not None
                               else spill_bytes_default())
        self.root = os.path.abspath(root) if root else None
        self._lock = threading.Lock()
        self._host_tier: "OrderedDict[bytes, Dict[str, onp.ndarray]]" = \
            OrderedDict()
        self._host_bytes = 0
        self._puts = 0
        self._demoted = 0
        self._dropped = 0
        self._remote_errors = 0
        self._sweep_every = 64
        self._remote_deadline_s = float(remote_deadline_s)
        self._server = None
        self._client = None
        if serve:
            from ..io.transport import BlockServer

            self._server = BlockServer(self._resolve, host=host,
                                       name=f"kvspill-{name}")
            self._server.start()
        if peers:
            self.set_peers(peers)

    # -- identity ----------------------------------------------------------
    @property
    def endpoint(self) -> Optional[str]:
        """``host:port`` of the serving side (None when not serving)."""
        return self._server.endpoint if self._server is not None else None

    def set_peers(self, peers: List[str]) -> None:
        """(Re)wire the remote tier's peer set. The disagg router calls
        this on every prefill-fleet scale/death event so decode engines
        always probe the *live* prefill exporters; an in-flight fetch
        on the old client is contained to a counted miss."""
        old, self._client = self._client, None
        if peers:
            from ..io.transport import BlockClient

            # the fetch budget is short on purpose: the engine probes
            # remote tiers from its admission path, and a dead peer
            # must cost a bounded miss, not a stall
            self._client = BlockClient(
                list(peers), deadline_s=self._remote_deadline_s)
        if old is not None:
            old.close()

    # -- the tiers ---------------------------------------------------------
    def put(self, hsh: bytes, arrays: Dict[str, onp.ndarray]) -> None:
        """Insert one evicted block's payload into the host tier
        (LRU-bump when already resident). Overflow beyond
        ``bytes_limit`` demotes oldest-first to the disk tier when one
        is armed, else drops."""
        nb = _nbytes(arrays)
        demote: List[Tuple[bytes, Dict[str, onp.ndarray]]] = []
        with self._lock:
            if hsh in self._host_tier:
                self._host_tier.move_to_end(hsh)
                return
            self._host_tier[hsh] = arrays
            self._host_bytes += nb
            self._puts += 1
            while self._host_bytes > self.bytes_limit and self._host_tier:
                h0, a0 = self._host_tier.popitem(last=False)
                self._host_bytes -= _nbytes(a0)
                demote.append((h0, a0))
        # disk IO outside the lock: a slow disk must never block a
        # concurrent put/get on the host tier
        for h0, a0 in demote:
            if self.root is not None:
                from ..io import cache as _iocache

                _iocache.blob_put(self.root, hash_hex(h0), _pack(a0))
                self._demoted += 1
                if self._demoted % self._sweep_every == 0:
                    # keep a shared root bounded to ~4x the host tier
                    _iocache.sweep_blob_root(
                        self.root, keep_bytes=4 * self.bytes_limit)
            else:
                self._dropped += 1

    def get(self, hsh: bytes
            ) -> Tuple[Optional[Dict[str, onp.ndarray]], Optional[str]]:
        """Probe host → disk → remote for one chain hash. Returns
        ``(payload, tier)`` on a hit (``tier`` in ``host``/``disk``/
        ``remote``; disk and remote hits are promoted into the host
        tier), ``(None, None)`` on a miss. Never raises: every
        transport/disk fault is contained to a miss."""
        with self._lock:
            a = self._host_tier.get(hsh)
            if a is not None:
                self._host_tier.move_to_end(hsh)
                return a, "host"
        if self.root is not None:
            from ..io import cache as _iocache

            blob = _iocache.blob_get(self.root, hash_hex(hsh))
            if blob is not None:
                a = _unpack(blob)
                if a is not None:
                    self._promote(hsh, a)
                    return a, "disk"
        client = self._client  # set_peers may swap it mid-probe
        if client is not None:
            try:
                blob = client.try_fetch("kv/" + hash_hex(hsh))
            except Exception:  # noqa: BLE001 — typed transport faults
                # retries exhausted / CRC-rejected garble / dead peer:
                # a remote miss, the engine re-prefills locally
                self._remote_errors += 1
                blob = None
            if blob is not None:
                a = _unpack(blob)
                if a is not None:
                    self._promote(hsh, a)
                    return a, "remote"
        return None, None

    def _promote(self, hsh: bytes, arrays: Dict[str, onp.ndarray]) -> None:
        """A lower-tier hit becomes a host-tier resident (the next hit
        is a memcpy, not a file read or a network round trip)."""
        nb = _nbytes(arrays)
        with self._lock:
            if hsh in self._host_tier:
                self._host_tier.move_to_end(hsh)
                return
            self._host_tier[hsh] = arrays
            self._host_bytes += nb
            while self._host_bytes > self.bytes_limit \
                    and len(self._host_tier) > 1:
                h0, a0 = self._host_tier.popitem(last=False)
                self._host_bytes -= _nbytes(a0)
                # promotion never demotes to disk: the evictee already
                # lives at (or below) the tier the hit came from

    # -- the serving side --------------------------------------------------
    def _resolve(self, name: str) -> Optional[bytes]:
        """BlockServer resolver: serve ``kv/<hex>`` from host or disk.
        Serialization runs outside the lock (only the dict lookup is
        inside); an unknown/garbled name is NOT_FOUND, never an
        error."""
        if not name.startswith("kv/"):
            return None
        try:
            hsh = bytes.fromhex(name[3:])
        except ValueError:
            return None
        with self._lock:
            a = self._host_tier.get(hsh)
            a = dict(a) if a is not None else None
        if a is not None:
            return _pack(a)
        if self.root is not None:
            from ..io import cache as _iocache

            return _iocache.blob_get(self.root, hash_hex(hsh))
        return None

    # -- accounting / lifecycle --------------------------------------------
    def level(self) -> Tuple[int, int]:
        """``(blocks, bytes)`` resident in the host tier (the gauges)."""
        with self._lock:
            return len(self._host_tier), self._host_bytes

    def stats(self) -> Dict:
        blocks, nbytes = self.level()
        out = {
            "host_blocks": blocks,
            "host_bytes": nbytes,
            "bytes_limit": self.bytes_limit,
            "puts": self._puts,
            "demoted_to_disk": self._demoted,
            "dropped": self._dropped,
            "remote_errors": self._remote_errors,
            "disk_root": self.root,
            "endpoint": self.endpoint,
        }
        client = self._client
        if client is not None:
            out["peers"] = list(client.endpoints)
        return out

    def close(self) -> None:
        if self._server is not None:
            self._server.close()
        if self._client is not None:
            self._client.close()
        with self._lock:
            self._host_tier.clear()
            self._host_bytes = 0
