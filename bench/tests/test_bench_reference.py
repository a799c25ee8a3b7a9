"""The plain reference is the model the program trains: same weights from a
seed, same loss and gradients, at a small size on the CPU."""

import _paths  # noqa: F401
import jax
import numpy as np

from benchlib.generator import Pool
from reference import mace as ref

TRAFFIC = {"mixture": [["water_clusters", 1.0, 4, 14, "amorphous", 0.8]],
           "graphs": 6, "size_seed": 5, "n_species": 10}


def _cfg(correlation):
    from repro.core.mace import MaceConfig

    return MaceConfig(n_species=10, channels=4, hidden_ls=(0, 1), sh_lmax=2,
                      a_ls=(0, 1, 2), correlation=correlation, n_interactions=2,
                      r_max=4.5, num_bessel=8, radial_mlp=(8, 8), readout_mlp=4,
                      avg_num_neighbors=6.0, impl="ref", interaction_impl="ref")


def test_weights_of_a_seed_are_the_programs():
    from repro.core.mace import init_mace

    cfg = _cfg(3)
    key = jax.random.PRNGKey(2**33 + 17)
    mine = ref.init_params(key, ref.Sizes.of(cfg))
    theirs = init_mace(key, cfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loss_and_gradients_are_the_programs():
    """On forces alone, the benchmark's configurations' loss, the reference
    and the program agree; the reference's energy term is MACE's mean over
    the real graphs, whatever the bin's graph slots."""
    from repro.core.mace import init_mace, weighted_loss
    from repro.data.collate import BinShape, collate_bin

    for correlation in (2, 3):
        cfg = _cfg(correlation)
        pool = Pool(TRAFFIC, cfg.r_max, seed=3)
        mols = [pool.get(i) for i in range(len(pool))]
        params = init_mace(jax.random.PRNGKey(1), cfg)
        shape = BinShape.for_capacity(128, 16, 8)
        batch = {k: jax.numpy.asarray(v) for k, v in collate_bin(mols, shape).items()}
        want, g_want = jax.value_and_grad(
            lambda p: weighted_loss(p, cfg, batch, 8, 0.0, 100.0)[0])(params)
        b = ref.collate(mols, 128, 128 * 16, 8)
        got, g_got = jax.value_and_grad(
            lambda p: ref.loss(p, ref.Sizes.of(cfg), b, 0.0, 100.0))(params)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        for a, c in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-4, atol=1e-6)
        energy = ref.loss(params, ref.Sizes.of(cfg), b, 1.0, 0.0)
        wider = ref.loss(params, ref.Sizes.of(cfg),
                         ref.collate(mols, 128, 128 * 16, 32), 1.0, 0.0)
        e = ref.energies(params, ref.Sizes.of(cfg),
                         {k: jax.numpy.asarray(v) for k, v in b.items()})
        per_atom = [(float(e[g]) - m.energy) / m.n_atoms for g, m in enumerate(mols)]
        np.testing.assert_allclose(float(energy), np.mean(np.square(per_atom)),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(wider), float(energy), rtol=1e-6)
