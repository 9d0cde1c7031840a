from predictionio_tpu.templates.sequence.engine import (
    DataSourceParams,
    Histories,
    ItemScore,
    PredictedResult,
    PreparatorParams,
    Query,
    SequenceAlgorithm,
    SequenceAlgorithmParams,
    SequenceDataSource,
    SequenceModel,
    SequencePreparator,
    engine,
)

__all__ = [
    "DataSourceParams",
    "Histories",
    "ItemScore",
    "PredictedResult",
    "PreparatorParams",
    "Query",
    "SequenceAlgorithm",
    "SequenceAlgorithmParams",
    "SequenceDataSource",
    "SequenceModel",
    "SequencePreparator",
    "engine",
]
