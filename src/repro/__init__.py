"""repro: BANG (billion-scale ANNS) on JAX.

Public API surface:
    repro.core.bang.BangIndex      -- the paper's three-stage ANNS pipeline
    BangIndex.executor(variant)    -- a resident, shape-bucketed search executor
    repro.runtime.ServePipeline    -- the streaming micro-batch serve loop
"""

__version__ = "0.1.0"
