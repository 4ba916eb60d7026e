from repro.kernels.grid_force.ops import (bin_vertices, choose_grid,
                                          grid_repulsion, grid_cell_size)
from repro.kernels.grid_force.ref import grid_near_ref, grid_far_ref
