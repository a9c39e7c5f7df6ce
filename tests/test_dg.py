"""DG solver physics + the nested-partition equivalence (paper's claim)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.dg.basis import diff_matrix, lgl_nodes_weights
from repro.dg.mesh import make_brick, two_tree_materials
from repro.dg.solver import DGSolver, gaussian_pulse, make_two_tree_solver


def test_lgl_quadrature_exactness():
    for N in (1, 2, 4, 7):
        x, w = lgl_nodes_weights(N)
        for k in range(2 * N):
            exact = 2 / (k + 1) if k % 2 == 0 else 0.0
            assert abs(np.sum(w * x**k) - exact) < 1e-12


def test_diff_matrix_exact_on_polynomials():
    for N in (2, 4, 7):
        x, _ = lgl_nodes_weights(N)
        D = diff_matrix(x)
        for k in range(1, N + 1):
            np.testing.assert_allclose(D @ x**k, k * x ** (k - 1), atol=1e-9)


@pytest.mark.parametrize(
    "name,cp,cs,comp",
    [("acoustic", (1.0, 1.0), (0.0, 0.0), 6),
     ("coupled", (1.0, 3.0), (0.0, 2.0), 6),
     ("elastic", (2.0, 2.0), (1.0, 1.0), 7)],
)
def test_energy_never_grows(name, cp, cs, comp):
    s = make_two_tree_solver(grid=(6, 4, 4), order=3, extent=(1.5, 1.0, 1.0), cp=cp, cs=cs)
    q0 = gaussian_pulse(s, center=(0.75, 0.5, 0.5), component=comp)
    e0 = s.energy(q0)
    q = s.run(q0, 30)
    e1 = s.energy(q)
    assert np.isfinite(e1) and e1 <= e0 * 1.0001, (name, e0, e1)


def test_plane_wave_p_convergence():
    """Spectral convergence of a periodic acoustic traveling wave."""
    errs = {}
    for order in (2, 4):
        mesh = make_brick((4, 2, 2), (1.0, 0.5, 0.5), periodic=True)
        K = mesh.K
        s = DGSolver(mesh=mesh, order=order, rho=np.ones(K), lam=np.ones(K), mu=np.zeros(K))
        xyz = s.node_coords()
        f = lambda x: np.sin(2 * np.pi * x)
        q0 = np.zeros((K, 9, s.M, s.M, s.M))
        q0[:, 6] = f(xyz[..., 0])
        q0[:, 0] = -f(xyz[..., 0])
        T = 0.2
        dt = s.cfl_dt(0.2)
        n = int(np.ceil(T / dt))
        q = s.run(jnp.asarray(q0), n, T / n)
        qe = np.zeros_like(q0)
        qe[:, 6] = f(xyz[..., 0] - T)
        qe[:, 0] = -f(xyz[..., 0] - T)
        errs[order] = float(jnp.abs(q - qe).max())
    assert errs[4] < errs[2] / 20, errs


def test_acoustic_region_has_zero_shear():
    """mu=0 in the acoustic half: the Riemann flux must use the k1=0 branch
    and shear stress stays ~0 there."""
    s = make_two_tree_solver(grid=(8, 4, 4), order=3, extent=(2.0, 1.0, 1.0))
    q0 = gaussian_pulse(s, center=(0.5, 0.5, 0.5), component=6)
    q = s.run(q0, 30)
    acoustic = np.asarray(s.mu == 0)
    shear = np.asarray(jnp.abs(q[:, 3:6]))  # E_yz, E_xz, E_xy
    # strain can be nonzero, but stress 2*mu*E == 0; check mu=0 elements
    assert np.isfinite(shear).all()
    S_shear = 2 * s.mu[:, None, None, None, None] * shear
    assert np.abs(S_shear[acoustic]).max() == 0.0


def test_nested_partition_equals_flat(subproc):
    subproc(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.dg.solver import make_two_tree_solver, gaussian_pulse
from repro.dg.partitioned import PartitionedDG
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
s = make_two_tree_solver(grid=(8,4,4), order=3, extent=(2.,1.,1.))
pdg = PartitionedDG(solver=s, mesh_axes=mesh)
rng = np.random.default_rng(0)
q0 = jnp.asarray(rng.standard_normal((s.mesh.K, 9, s.M, s.M, s.M)))
err = np.abs(np.asarray(s.rhs(q0)) - pdg.permute_out(np.asarray(pdg.rhs(pdg.permute_in(q0))))).max()
assert err < 1e-11, err
qg = gaussian_pulse(s, center=(0.9,0.5,0.5), component=6)
qf = s.run(qg, 30)
qp = pdg.run(pdg.permute_in(qg), 30)
err = float(jnp.abs(qf - pdg.permute_out(np.asarray(qp))).max())
assert err < 1e-10, err
print("OK")
""",
        n_devices=4,
    )


def test_two_tree_materials_split():
    mesh = make_brick((8, 4, 4), (2.0, 1.0, 1.0))
    rho, lam, mu, region = two_tree_materials(mesh)
    assert (mu[region == 0] == 0).all()  # acoustic half
    assert (mu[region == 1] > 0).all()  # elastic half
    assert region.sum() == mesh.K // 2


def test_solver_with_pallas_kernel_matches_xla():
    """kernel_impl='interpret' (the Pallas volume_loop body) == jnp path."""
    s1 = make_two_tree_solver(grid=(4, 2, 2), order=3)
    s2 = make_two_tree_solver(grid=(4, 2, 2), order=3, kernel_impl="interpret")
    q = gaussian_pulse(s1, center=(1.0, 0.5, 0.5))
    np.testing.assert_allclose(s1.rhs(q), s2.rhs(q), rtol=1e-10, atol=1e-10)
