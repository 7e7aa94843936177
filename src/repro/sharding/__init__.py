from .rules import (batch_pspecs, cache_pspecs, data_axes, opt_pspecs,
                    param_pspecs, shard_if_divisible)

__all__ = ["batch_pspecs", "cache_pspecs", "data_axes", "opt_pspecs",
           "param_pspecs", "shard_if_divisible"]
