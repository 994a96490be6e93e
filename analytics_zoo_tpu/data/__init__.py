"""Data layer: XShards, file readers, device feed (reference L4, SURVEY.md §2.2)."""

from .feed import (DataFeed, EpochEnd, PrefetchIterator, as_feed,
                   batch_sharding, shard_batch)
from .readers import (FileReadahead, read_csv, read_json, read_npz,
                      read_parquet)
from .shards import XShards
from .stream import StreamingDataFeed, make_placer
from .shm_pool import ShmBatchPool, SlotBatch
from .augment import (DeviceAugment, DeviceNormalize, DeviceRandomCrop,
                      DeviceRandomFlip)
from .image import (ImageSet, ImageResize, ImageCenterCrop, ImageRandomCrop,
                    ImageRandomFlip, ImageNormalize, ImageBrightness,
                    ImageContrast, ImageSaturation, ImageColorJitter)
from .text import TextSet
from .interop import (IterableDataFeed, from_iterator, from_tf_dataset,
                      from_torch_dataset, from_torch_dataloader)

# reference-parity namespace: zoo.orca.data.pandas.read_csv
from . import readers as pandas  # noqa: F401

__all__ = [
    "XShards", "DataFeed", "EpochEnd", "PrefetchIterator", "as_feed",
    "batch_sharding", "shard_batch",
    "read_csv", "read_json", "read_npz", "read_parquet", "pandas",
    "FileReadahead", "StreamingDataFeed", "make_placer", "ShmBatchPool",
    "SlotBatch", "DeviceAugment", "DeviceNormalize", "DeviceRandomCrop",
    "DeviceRandomFlip", "ImageSet", "ImageResize", "ImageCenterCrop",
    "ImageRandomCrop", "ImageRandomFlip", "ImageNormalize", "ImageBrightness",
    "ImageContrast", "ImageSaturation", "ImageColorJitter", "TextSet",
    "IterableDataFeed", "from_iterator", "from_tf_dataset",
    "from_torch_dataset", "from_torch_dataloader",
]
