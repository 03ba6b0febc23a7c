from repro.models.common import (ModelConfig, Param, cross_entropy,
                                 is_param, split_params)
from repro.models.transformer import (abstract_params, cache_axes,
                                      forward, init_model,
                                      init_model_cache, init_params,
                                      init_params_and_axes)

__all__ = ["ModelConfig", "Param", "cross_entropy", "is_param",
           "split_params", "abstract_params", "cache_axes", "forward",
           "init_model", "init_model_cache", "init_params",
           "init_params_and_axes"]
