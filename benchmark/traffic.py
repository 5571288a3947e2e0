"""The one traffic generator: a mix file in, query texts out.

A mix (``benchmark/traffic/<name>.json``) is a closed loop of ``clients``
callers that take their next request from one queue. The queue is a sequence
of blocks; a block holds each class ``per_block`` times, shuffled from the
seed (``order: shuffled``) or in the order written (``order: replay``). So
every seed sends the same set of requests in another order, and a class's
share is exact and not drawn. A class is a query file under
``benchmark/queries/``; a ``%prefix:Type`` placeholder in it is replaced,
request by request, by an instance of that type drawn uniformly. The program
gets the texts and nothing else; the ids drawn are kept for the reference.
"""

from __future__ import annotations

import re
import threading

import numpy as np

from benchmark.reference import PREFIX
from benchmark.spec import SpecError, query_text

_PLACEHOLDER = re.compile(r"%(\w*):(\w+)")

# independent streams of one seed
STREAM_QUEUE, STREAM_SAMPLE, STREAM_WARM = 11, 12, 13


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


class Request:
    __slots__ = ("idx", "cls", "kind", "text")

    def __init__(self, idx: int, cls: str, kind: str, text: str):
        self.idx, self.cls, self.kind, self.text = idx, cls, kind, text


class _Class:
    def __init__(self, entry: dict, instances, id2str):
        self.name = entry["name"]
        self.kind = entry["kind"]
        self.per_block = int(entry.get("per_block", 1))
        self.template = query_text(entry["file"])
        self.constants: dict[str, int] = {}  # iri -> id, as drawn
        m = _PLACEHOLDER.search(self.template)
        self.slot = m.group(0) if m else None
        if m is None:
            return
        if len(set(_PLACEHOLDER.findall(self.template))) != 1:
            raise SpecError(f"{entry['file']}: one placeholder type a "
                            "template, please")
        prefixes = dict(PREFIX.findall(self.template))
        if m.group(1) not in prefixes:
            raise SpecError(f"{entry['file']}: placeholder {self.slot} uses "
                            "an undeclared prefix")
        self.pool = instances(f"<{prefixes[m.group(1)]}{m.group(2)}>")
        if len(self.pool) == 0:
            raise SpecError(f"{entry['file']}: no instance of {self.slot}")
        self.id2str = id2str
        draw = entry.get("draw", {"dist": "uniform"})
        if draw["dist"] != "uniform":
            raise SpecError(f"unknown draw {draw!r} in class {self.name}")

    def text(self, rng: np.random.Generator) -> str:
        if self.slot is None:
            return self.template
        vid = int(self.pool[rng.integers(0, len(self.pool))])
        iri = self.id2str(vid)
        self.constants[iri] = vid
        return self.template.replace(self.slot, iri)


class Traffic:
    """The queue of one run. ``take`` is safe to call from the clients."""

    def __init__(self, mix: dict, seed: int, instances, id2str):
        if mix.get("loop") != "closed":
            raise SpecError("only closed loops are generated: "
                            f"loop={mix.get('loop')!r}")
        self.clients = int(mix["clients"])
        self.order = mix.get("order", "shuffled")
        if self.order not in ("shuffled", "replay"):
            raise SpecError(f"unknown order {self.order!r}")
        self.close = mix.get("close", "reply")
        if self.close not in ("reply", "cycle"):
            raise SpecError(f"unknown close {self.close!r}")
        self.classes = [_Class(e, instances, id2str)
                        for e in mix["classes"]]
        self.block = [c for c in self.classes for _ in range(c.per_block)]
        self.seed = seed
        self._rng = rng_for(seed, STREAM_QUEUE)
        self._lock = threading.Lock()
        self._buf: list[Request] = []
        self._next = 0
        self.warm_draws = int(mix.get("warm_draws", 1))
        self.warm_passes_max = int(mix.get("warm_passes_max", 3))

    def _refill(self) -> None:
        if self.order == "shuffled":
            order = self._rng.permutation(len(self.block))
        else:
            order = range(len(self.block))
        for j in order:
            c = self.block[j]
            self._buf.append(Request(self._next, c.name, c.kind,
                                     c.text(self._rng)))
            self._next += 1

    def take(self, closing: bool = False) -> Request | None:
        """The next request; ``None`` once the window is ``closing`` and, for
        ``close: cycle``, the block in hand has been given out whole."""
        with self._lock:
            # the buffer is filled a block at a time: empty is a block's start
            if closing and (self.close == "reply" or not self._buf):
                return None
            if not self._buf:
                self._refill()
            return self._buf.pop(0)

    def warm_pass(self, k: int) -> list[Request]:
        """Pass ``k`` of the warm-up: every class ``warm_draws`` times with
        constants of a stream of their own."""
        rng = rng_for(self.seed, STREAM_WARM + 1000 * k)
        return [Request(-1, c.name, c.kind, c.text(rng))
                for c in self.classes
                for _ in range(self.warm_draws if c.slot else 1)]

    def constants(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for c in self.classes:
            out.update(c.constants)
        return out
