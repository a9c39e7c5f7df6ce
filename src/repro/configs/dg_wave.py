"""The paper's own evaluation problem: DGSEM coupled elastic-acoustic wave
propagation on a brick with a centered material discontinuity (Fig 6.1),
order N=7, 8192 elements per node (Table 6.1)."""

import dataclasses


@dataclasses.dataclass(frozen=True)
class DGConfig:
    order: int = 7
    grid: tuple = (32, 16, 16)          # 8192 elements (one node's share)
    n_nodes: int = 1                    # level-1 partitions
    accel_ratio: float = 1.6            # published K_MIC/K_CPU optimum
    # two material trees (Fig 6.1): acoustic cp=1 cs=0 | elastic cp=3 cs=2
    cp: tuple = (1.0, 3.0)
    cs: tuple = (0.0, 2.0)
    rho: tuple = (1.0, 1.0)
    # the run's horizon, each step at the solver's CFL step
    # (DGSolver.cfl_dt, ~1.28e-4 on this brick): a fixed dt = 1e-3 is 8x
    # over that bound at order 7 and the run blows up
    n_steps: int = 118


CONFIG = DGConfig()
