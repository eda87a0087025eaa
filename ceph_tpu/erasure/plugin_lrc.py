"""LRC plugin: Locally Repairable Codes via layered composition.

Semantics follow the reference
(/root/reference/src/erasure-code/lrc/ErasureCodeLrc.cc): a `mapping`
string assigns every chunk position a role ('D' data, anything else
coding/pad), and `layers` is a JSON list of [layer_mapping, profile]
pairs, each layer an independent sub-code over the positions its
mapping marks 'D' (inputs) and 'c' (outputs).  The convenience k/m/l
form generates one global layer plus (k+m)/l local layers exactly like
parse_kml (:280-360), so a local failure repairs from l chunks instead
of k.

Where every layer is a byte matrix the code IS the matrix codec's
technique `lrc` (erasure/matrix_codec.py: the layers composed to one
generator, the plan layer by layer, the chunk mapping), and this
plugin is a factory over it: `plugin=lrc k=4 m=2 l=3` and `plugin=tpu
technique=lrc k=4 m=2 l=3` give the same bytes at the same positions.
A layer that does not compose (a packet technique, a sub-plugin with
a decoder of its own) keeps the layered host code below: one sub-codec
call a layer, minimum_to_decode the cheapest layer per missing chunk
(:554).
"""

from __future__ import annotations

from typing import Mapping

import numpy as np

from .interface import ErasureCode, ErasureCodeError
from .matrix_codec import (TECHNIQUES, MatrixErasureCode, TpuBackend,
                           chunk_mapping, lrc_layout)
from .plugin_jerasure import backend_from_profile
from .registry import ErasureCodePlugin


class ErasureCodeLrcMatrix(MatrixErasureCode):
    DEFAULT_TECHNIQUE = "lrc"

    def __init__(self, backend=None):
        super().__init__(backend=backend or TpuBackend(),
                         techniques={"lrc": TECHNIQUES["lrc"]})


class _Layer:
    def __init__(self, mapping: str, codec, positions: list[int]):
        self.mapping = mapping           # over global positions
        self.codec = codec               # sub-plugin instance
        self.data_positions = [p for p in positions if mapping[p] == "D"]
        self.coding_positions = [p for p in positions if mapping[p] == "c"]
        # codec chunk id order: data chunks first, then coding chunks
        self.positions = self.data_positions + self.coding_positions

    def local_index(self, global_pos: int) -> int:
        return self.positions.index(global_pos)


class ErasureCodeLrc(ErasureCode):
    DEFAULT_SUBPLUGIN = "jerasure"

    def __init__(self, registry):
        self._registry = registry
        self.mapping = ""
        self.layers: list[_Layer] = []

    # -- init --------------------------------------------------------------

    def init(self, profile: Mapping[str, str]) -> None:
        self.mapping, layer_desc = lrc_layout(profile)
        self.k = self.mapping.count("D")
        self.m = len(self.mapping) - self.k
        self.layers = []
        for lmap, lprofile in layer_desc:
            positions = [i for i, ch in enumerate(lmap) if ch in ("D", "c")]
            lprofile = dict(lprofile)
            lprofile.setdefault("plugin", self.DEFAULT_SUBPLUGIN)
            # layers are many SMALL codes: sub-codecs pin the native
            # host path unless the profile explicitly asks for a
            # device-routed layer backend
            lprofile.setdefault("backend", "host")
            lprofile["k"] = str(lmap.count("D"))
            lprofile["m"] = str(lmap.count("c"))
            sub = self._registry.factory(lprofile.pop("plugin"), lprofile)
            self.layers.append(_Layer(lmap, sub, positions))

    # -- geometry ----------------------------------------------------------

    def get_chunk_count(self) -> int:
        return len(self.mapping)

    def get_chunk_mapping(self) -> list[int]:
        return chunk_mapping(self.mapping)

    def get_alignment(self) -> int:
        return self.k * max(layer.codec.get_alignment() // max(layer.codec.k, 1)
                            for layer in self.layers)

    def get_chunk_size(self, object_size: int) -> int:
        alignment = self.get_alignment()
        padded = -(-object_size // alignment) * alignment
        return padded // self.k

    # -- encode ------------------------------------------------------------

    def encode(self, want_to_encode, data) -> dict[int, np.ndarray]:
        chunks = self.encode_prepare(data)      # (k, L)
        L = chunks.shape[1]
        n = self.get_chunk_count()
        buf = np.zeros((n, L), dtype=np.uint8)
        data_pos = [i for i, ch in enumerate(self.mapping) if ch == "D"]
        for i, pos in enumerate(data_pos):
            buf[pos] = chunks[i]
        for layer in self.layers:
            if not layer.coding_positions:
                continue
            lin = buf[np.asarray(layer.data_positions)]
            parity = layer.codec.encode_chunks(lin)
            for idx, pos in enumerate(layer.coding_positions):
                buf[pos] = parity[idx]
        mapping = self.get_chunk_mapping()
        out = {}
        for i in want_to_encode:
            if not 0 <= i < n:
                raise ErasureCodeError(f"chunk id {i} out of range")
            out[i] = buf[mapping[i]]
        return out

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        data_chunks = np.asarray(data_chunks, dtype=np.uint8)
        L = data_chunks.shape[1]
        n = self.get_chunk_count()
        buf = np.zeros((n, L), dtype=np.uint8)
        data_pos = [i for i, ch in enumerate(self.mapping) if ch == "D"]
        for i, pos in enumerate(data_pos):
            buf[pos] = data_chunks[i]
        for layer in self.layers:
            if not layer.coding_positions:
                continue
            lin = buf[np.asarray(layer.data_positions)]
            parity = layer.codec.encode_chunks(lin)
            for idx, pos in enumerate(layer.coding_positions):
                buf[pos] = parity[idx]
        other_pos = [i for i, ch in enumerate(self.mapping) if ch != "D"]
        return buf[np.asarray(other_pos)]

    # -- decode ------------------------------------------------------------

    def _position_of(self, chunk_id: int) -> int:
        return self.get_chunk_mapping()[chunk_id]

    def minimum_to_decode(self, want_to_read, available) -> list[int]:
        mapping = self.get_chunk_mapping()
        inv = {pos: cid for cid, pos in enumerate(mapping)}
        want_pos = {mapping[int(i)] for i in want_to_read}
        avail_pos = {mapping[int(i)] for i in available}
        need = set(p for p in want_pos if p in avail_pos)
        missing = want_pos - avail_pos
        for pos in sorted(missing):
            best = None
            for layer in self.layers:
                lset = set(layer.positions)
                if pos not in lset:
                    continue
                lavail = [layer.local_index(p) for p in lset & avail_pos]
                try:
                    lmin = layer.codec.minimum_to_decode(
                        [layer.local_index(pos)], lavail)
                except ErasureCodeError:
                    continue
                cost = {layer.positions[i] for i in lmin}
                if best is None or len(cost) < len(best):
                    best = cost
            if best is None:
                raise ErasureCodeError(
                    f"cannot decode position {pos} from {sorted(avail_pos)}")
            need |= best
        return sorted(inv[p] for p in need)

    def decode_chunks(self, want_to_read, chunks) -> dict[int, np.ndarray]:
        mapping = self.get_chunk_mapping()
        inv = {pos: cid for cid, pos in enumerate(mapping)}
        have_pos = {mapping[int(i)]: np.asarray(b, dtype=np.uint8)
                    for i, b in chunks.items()}
        want = [int(i) for i in want_to_read]
        # iterate layers until every wanted position is materialized:
        # repairing one position may unlock another layer's repair
        progress = True
        want_pos = {mapping[i] for i in want}
        while progress and not want_pos <= have_pos.keys():
            progress = False
            for layer in self.layers:
                lset = set(layer.positions)
                for p in sorted(lset - have_pos.keys()):
                    lhave = {layer.local_index(q): have_pos[q]
                             for q in lset & have_pos.keys()}
                    try:
                        rebuilt = layer.codec.decode_chunks(
                            [layer.local_index(p)], lhave)
                    except ErasureCodeError:
                        continue
                    arr = rebuilt[layer.local_index(p)]
                    have_pos[p] = np.asarray(arr, dtype=np.uint8)
                    progress = True
        missing = [i for i in want if mapping[i] not in have_pos]
        if missing:
            raise ErasureCodeError(f"cannot reconstruct chunks {missing}")
        return {i: have_pos[mapping[i]] for i in want}


class ErasureCodeLrcPlugin(ErasureCodePlugin):
    def __init__(self, registry):
        self._registry = registry

    def factory(self, profile):
        try:
            TECHNIQUES["lrc"][0](profile)
        except ErasureCodeError:
            # a layer that is no byte matrix (or a profile whose fault
            # the layered init reports)
            return ErasureCodeLrc(self._registry)
        return ErasureCodeLrcMatrix(backend=backend_from_profile(profile))


def __erasure_code_init__(registry, name):
    registry.add(name, ErasureCodeLrcPlugin(registry))
