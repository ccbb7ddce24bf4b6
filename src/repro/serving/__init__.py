from repro.serving.engine import (ContinuousBatchingEngine,  # noqa
                                  ContinuousResult)
from repro.serving import cot, kv_pool, prefix_cache, sampling, \
    scheduler  # noqa
