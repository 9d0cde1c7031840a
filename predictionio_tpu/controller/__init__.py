"""Controller API — the user-facing engine SDK (DASE).

Reference: core/src/main/scala/org/apache/predictionio/controller/
(SURVEY.md §2.1 "Controller API").  Engine authors import from here::

    from predictionio_tpu.controller import (
        DataSource, Preparator, Algorithm, Serving, Engine, Params, ...
    )
"""

from predictionio_tpu.controller.base import (
    Algorithm,
    DataSource,
    FirstServing,
    IdentityPreparator,
    PersistentModel,
    Preparator,
    RuntimeContext,
    Serving,
    WarmStartFallback,
    model_from_bytes,
    model_to_bytes,
)
from predictionio_tpu.controller.columns import ItemScoreColumns
from predictionio_tpu.controller.engine import (
    Engine,
    EngineParams,
    EngineVariant,
    load_engine_factory,
)
from predictionio_tpu.controller.metrics import (
    AverageMetric,
    EngineParamsGenerator,
    Evaluation,
    Metric,
    MetricEvaluatorResult,
    OptionAverageMetric,
    SumMetric,
    ZeroMetric,
)
from predictionio_tpu.controller.params import (
    EmptyParams,
    Params,
    ParamsBindingError,
    bind_params,
    params_to_dict,
)

__all__ = [
    "Algorithm",
    "AverageMetric",
    "DataSource",
    "EmptyParams",
    "Engine",
    "EngineParams",
    "EngineParamsGenerator",
    "EngineVariant",
    "Evaluation",
    "FirstServing",
    "IdentityPreparator",
    "ItemScoreColumns",
    "Metric",
    "MetricEvaluatorResult",
    "OptionAverageMetric",
    "Params",
    "ParamsBindingError",
    "PersistentModel",
    "Preparator",
    "RuntimeContext",
    "Serving",
    "SumMetric",
    "WarmStartFallback",
    "ZeroMetric",
    "bind_params",
    "load_engine_factory",
    "model_from_bytes",
    "model_to_bytes",
    "params_to_dict",
]
