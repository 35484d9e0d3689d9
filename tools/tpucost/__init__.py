"""tpucost — static program-cost analyzer and CI perf-regression gate.

The third analyzer in the lint/audit/cost/shard/sync quintet. tpulint reads SOURCE,
tpuaudit reads the PROGRAM's semantics (collectives, donation, dtypes);
tpucost reads the program's COST: it AOT-compiles every entry in the
tpuaudit registry host-side and extracts XLA's own cost and memory analysis
— flops, bytes accessed, peak/temp/argument HBM, collective payload bytes
per mesh axis, op counts, program size — then derives an analytic roofline
bound (predicted step time, MFU ceiling). Gated in CI against a committed
``.tpucost-baseline.json`` with per-metric tolerance bands, so a program
that silently got fatter (a dropped donation, an undeclared reshard, a
dtype widening) fails the PR without a chip, and the autotuner
gets a measured cost vector instead of its static tables.
"""

from .baseline import TOLERANCES, CostFinding
from .core import (CostVector, cost_entry, publish_vectors,
                   registry_cost_vector, run_cost)
from .extract import (collective_census, cost_analysis_dict, hlo_op_census,
                      memory_analysis_dict, program_hash)
from .roofline import RooflineBound, roofline

__all__ = [
    "TOLERANCES", "CostFinding", "CostVector", "cost_entry",
    "publish_vectors", "registry_cost_vector", "run_cost",
    "collective_census", "cost_analysis_dict", "hlo_op_census",
    "memory_analysis_dict", "program_hash", "RooflineBound", "roofline",
]
