from .rules import (batch_specs, cache_specs, data_axes, named, opt_specs,
                    param_specs)

__all__ = ["batch_specs", "cache_specs", "data_axes", "named", "opt_specs",
           "param_specs"]
