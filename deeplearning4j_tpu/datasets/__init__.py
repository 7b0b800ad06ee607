"""Host-side data pipeline (parity: deeplearning4j-nn/.../datasets/iterator
+ deeplearning4j-core dataset fetchers, SURVEY.md §2.5)."""

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    DataSetIterator,
    ListDataSetIterator,
    ArrayDataSetIterator,
    AsyncDataSetIterator,
    DevicePrefetchIterator,
    MultipleEpochsIterator,
    SamplingDataSetIterator,
    ReconstructionDataSetIterator,
)
from deeplearning4j_tpu.datasets.preprocessors import (
    BlockDiffusionPreProcessor,
    PreProcessingIterator,
)
from deeplearning4j_tpu.datasets.fetchers import (
    CifarDataSetIterator,
    CurvesDataSetIterator,
    IrisDataSetIterator,
    LFWDataSetIterator,
    MnistDataSetIterator,
)
