"""Synthetic molecular traffic: the Table-3 mixture of the MACE CFM paper.

A copy of the program's generator (``data/molecules.py``), kept with the
benchmark as its yardstick and driven by a traffic file's parameters:

* ``mixture``: rows ``[name, proportion, min_atoms, max_atoms, packing,
  density]``, and optionally a seventh column, the row's size histogram:
  ``[[lo, hi, weight], ...]``, uniform within each range (without it, sizes
  are uniform on ``min_atoms .. max_atoms``); packing ``lattice`` is a
  jittered cubic grid, ``amorphous`` uniform points at the density's number
  density;
* ``graphs``: how many structures the pool holds;
* ``size_seed``: draws which system and size each structure has.  It is
  fixed in the file, so every run seed trains on the same multiset of sizes
  (and so the same Algorithm-1 bins) and the seed changes only the order
  of the pool, the positions and the species;
* ``order_seed``: the seed of the sampler's epoch order of the bins (read
  by the driver), fixed in the file for the same reason;
* ``n_species``: species are drawn from ``0 .. n_species - 1``.

Edges are every ordered pair within the model's cutoff.  Labels come from a
smooth pair potential with exact forces, so training has a signal.  All
structures are built during set-up and held in memory, as a preprocessed
dataset would be: ``get(i)`` is a lookup.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np
from scipy.spatial import cKDTree

TARGET_SPACING = 2.4  # Å, typical interatomic distance


@dataclasses.dataclass
class Structure:
    species: np.ndarray    # [n] int32
    positions: np.ndarray  # [n, 3] float32
    senders: np.ndarray    # [e] int32, both directions of every pair
    receivers: np.ndarray  # [e] int32
    energy: float
    forces: np.ndarray     # [n, 3] float32
    system: str

    @property
    def n_atoms(self) -> int:
        return len(self.species)

    @property
    def n_edges(self) -> int:
        return len(self.senders)


def draw_sizes(mixture: Sequence, n: int, size_seed: int):
    """(system index, atom count) of ``n`` structures, from ``size_seed``."""
    rng = np.random.default_rng(size_seed)
    props = np.array([float(m[1]) for m in mixture])
    system = rng.choice(len(mixture), size=n, p=props / props.sum())
    u = rng.random(n)
    sizes = np.zeros(n, np.int64)
    for k, row in enumerate(mixture):
        hist = row[6] if len(row) > 6 else [[row[2], row[3], 1.0]]
        lo, hi, w = (np.array([float(h[j]) for h in hist]) for j in range(3))
        pick = system == k
        part = rng.choice(len(hist), size=int(pick.sum()), p=w / w.sum())
        size = lo[part] + u[pick] * (hi[part] - lo[part] + 1)
        sizes[pick] = np.minimum(size.astype(np.int64), hi[part].astype(np.int64))
    return system, sizes


def cutoff_edges(pos: np.ndarray, r_cut: float):
    """Directed edges (both directions) between atoms closer than r_cut."""
    if len(pos) < 2:
        z = np.zeros((0,), np.int32)
        return z, z.copy()
    pairs = cKDTree(pos).query_pairs(r_cut, output_type="ndarray")
    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    snd = np.concatenate([pairs[:, 0], pairs[:, 1]]).astype(np.int32)
    rcv = np.concatenate([pairs[:, 1], pairs[:, 0]]).astype(np.int32)
    return snd, rcv


def pair_potential(pos, senders, receivers, r_cut):
    """phi(r) = (1 - r / r_cut)^2 summed over pairs, and its exact forces."""
    if len(senders) == 0:
        return 0.0, np.zeros_like(pos)
    vec = pos[receivers] - pos[senders]
    r = np.linalg.norm(vec, axis=1)
    x = np.clip(r / r_cut, 1e-6, 1.0)
    e = 0.5 * np.sum((1 - x) ** 2)
    dedr = -2.0 * (1 - x) / r_cut
    f_edge = (0.5 * dedr / np.maximum(r, 1e-9))[:, None] * vec
    n = len(pos)
    forces = np.stack([np.bincount(senders, f_edge[:, c], n)
                       - np.bincount(receivers, f_edge[:, c], n)
                       for c in range(3)], axis=1)
    return e, forces


def build(name: str, n: int, packing: str, density: float, n_species: int,
          r_cut: float, rng: np.random.Generator) -> Structure:
    spacing = TARGET_SPACING / density ** (1.0 / 3.0)
    if packing == "lattice":
        side = int(np.ceil(n ** (1.0 / 3.0)))
        grid = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                        -1).reshape(-1, 3)[:n]
        pos = grid * spacing + rng.normal(0, 0.08 * spacing, (n, 3))
    elif packing == "amorphous":
        box = spacing * max(n, 2) ** (1.0 / 3.0) * 1.12
        pos = rng.random((n, 3)) * box
    else:
        raise ValueError(f"unknown packing {packing!r}")
    species = rng.integers(0, n_species, n).astype(np.int32)
    snd, rcv = cutoff_edges(pos, r_cut)
    energy, forces = pair_potential(pos, snd, rcv, r_cut)
    return Structure(species, pos.astype(np.float32), snd, rcv, float(energy),
                     forces.astype(np.float32), name)


class Pool:
    """The structures of one run, in the seed's order (``sizes``, ``get``
    and ``len`` are what the program's samplers and trainer read)."""

    def __init__(self, traffic: dict, r_cut: float, seed: int):
        mixture = traffic["mixture"]
        system, sizes = draw_sizes(mixture, int(traffic["graphs"]),
                                   int(traffic["size_seed"]))
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(sizes))
        self.structures: List[Structure] = []
        for i in order:
            name, _, _, _, packing, density = mixture[system[i]][:6]
            self.structures.append(build(
                name, int(sizes[i]), packing, float(density),
                int(traffic["n_species"]), r_cut, rng))
        self.sizes = np.array([m.n_atoms for m in self.structures], np.int64)
        self.n_edges = np.array([m.n_edges for m in self.structures], np.int64)

    def __len__(self) -> int:
        return len(self.structures)

    def get(self, i: int) -> Structure:
        return self.structures[i]
