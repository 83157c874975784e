"""The Spark SQL configuration spaces.

``spark_space`` is the paper's 60-knob space (§7.1: Tuneful's space
extended to 60 performance-relevant parameters). ``spark35_space`` is those
60 knobs, in order, then 68 further settings documented for Apache Spark
3.5 (``configuration.html`` and ``sql-performance-tuning.html``): a
128-knob space of the width a Spark 3.5 warehouse exposes.

Roughly a third of the knobs drive the cost model strongly (the realistic
regime — most Spark knobs barely matter for a given workload, which is
exactly why the paper's knob-selection mechanism exists). The remainder
have small or negligible effects so that compression must *discover*
importance rather than being handed it.
"""

from __future__ import annotations

from ..core.space import BoolKnob, CatKnob, ConfigSpace, FloatKnob, IntKnob

__all__ = ["spark_space", "spark35_space", "INFLUENTIAL_KNOBS",
           "SPARK35_INFLUENTIAL_KNOBS", "SPARK35_DEFAULTS"]


# knobs the cost model gives first-order effects to
INFLUENTIAL_KNOBS = [
    "spark.executor.instances",
    "spark.executor.cores",
    "spark.executor.memory",
    "spark.executor.memoryOverhead",
    "spark.memory.fraction",
    "spark.memory.storageFraction",
    "spark.sql.shuffle.partitions",
    "spark.sql.files.maxPartitionBytes",
    "spark.sql.autoBroadcastJoinThreshold",
    "spark.io.compression.codec",
    "spark.serializer",
    "spark.shuffle.compress",
    "spark.sql.adaptive.enabled",
    "spark.sql.adaptive.coalescePartitions.enabled",
    "spark.sql.adaptive.skewJoin.enabled",
    "spark.reducer.maxSizeInFlight",
    "spark.shuffle.file.buffer",
    "spark.speculation",
    "spark.locality.wait",
    "spark.default.parallelism",
]


def spark_space() -> ConfigSpace:
    knobs = [
        # ---- resource sizing (first order). Defaults model a plausible
        # ops-team baseline (the paper's "default Spark configuration"),
        # suboptimal by the 2-4x the paper reports, not pathological.
        IntKnob("spark.executor.instances", 2, 48, default=12),
        IntKnob("spark.executor.cores", 1, 16, default=4),
        IntKnob("spark.executor.memory", 2, 64, log=True, default=12),           # GB
        IntKnob("spark.executor.memoryOverhead", 384, 8192, log=True, default=384),  # MB
        FloatKnob("spark.memory.fraction", 0.3, 0.9, default=0.6),
        FloatKnob("spark.memory.storageFraction", 0.1, 0.9, default=0.5),
        # ---- parallelism / partitioning (first order)
        IntKnob("spark.sql.shuffle.partitions", 20, 4000, log=True, default=200),
        IntKnob("spark.default.parallelism", 20, 2000, log=True, default=200),
        IntKnob("spark.sql.files.maxPartitionBytes", 16, 1024, log=True, default=128),  # MB
        IntKnob("spark.sql.autoBroadcastJoinThreshold", 0, 512, default=10),     # MB, 0=off
        # ---- shuffle & IO (first order)
        CatKnob("spark.io.compression.codec", ("lz4", "snappy", "zstd"), default="lz4"),
        CatKnob("spark.serializer", ("java", "kryo"), default="java"),
        BoolKnob("spark.shuffle.compress", default=True),
        IntKnob("spark.reducer.maxSizeInFlight", 8, 256, log=True, default=48),  # MB
        IntKnob("spark.shuffle.file.buffer", 16, 1024, log=True, default=32),    # KB
        # ---- adaptive execution (first order)
        BoolKnob("spark.sql.adaptive.enabled", default=True),
        BoolKnob("spark.sql.adaptive.coalescePartitions.enabled", default=True),
        BoolKnob("spark.sql.adaptive.skewJoin.enabled", default=False),
        # ---- scheduling (moderate)
        BoolKnob("spark.speculation", default=False),
        FloatKnob("spark.locality.wait", 0.0, 10.0, default=3.0),                # s
        # ---- moderate / second order
        BoolKnob("spark.shuffle.spill.compress", default=True),
        IntKnob("spark.kryoserializer.buffer.max", 8, 256, log=True, default=64),  # MB
        IntKnob("spark.sql.inMemoryColumnarStorage.batchSize", 1000, 100000, log=True, default=10000),
        BoolKnob("spark.sql.inMemoryColumnarStorage.compressed", default=True),
        IntKnob("spark.shuffle.io.numConnectionsPerPeer", 1, 8, default=1),
        IntKnob("spark.shuffle.sort.bypassMergeThreshold", 50, 1000, default=200),
        BoolKnob("spark.memory.offHeap.enabled", default=False),
        IntKnob("spark.memory.offHeap.size", 0, 16384, default=0),               # MB
        IntKnob("spark.broadcast.blockSize", 1, 32, default=4),                  # MB
        IntKnob("spark.sql.broadcastTimeout", 120, 1200, default=300),           # s
        FloatKnob("spark.speculation.multiplier", 1.1, 5.0, default=1.5),
        FloatKnob("spark.speculation.quantile", 0.5, 0.95, default=0.75),
        # ---- long tail (negligible effect in the model; must be pruned)
        IntKnob("spark.rpc.askTimeout", 30, 600, default=120),
        IntKnob("spark.network.timeout", 60, 800, default=120),
        IntKnob("spark.storage.memoryMapThreshold", 1, 16, default=2),           # MB
        IntKnob("spark.locality.wait.node", 0, 10, default=3),
        IntKnob("spark.locality.wait.rack", 0, 10, default=3),
        IntKnob("spark.scheduler.revive.interval", 1, 10, default=1),
        IntKnob("spark.task.maxFailures", 1, 8, default=4),
        IntKnob("spark.stage.maxConsecutiveAttempts", 2, 8, default=4),
        BoolKnob("spark.shuffle.service.enabled", default=False),
        IntKnob("spark.shuffle.registration.timeout", 500, 10000, default=5000),
        IntKnob("spark.cleaner.periodicGC.interval", 10, 60, default=30),
        BoolKnob("spark.rdd.compress", default=False),
        IntKnob("spark.io.compression.lz4.blockSize", 8, 128, default=32),       # KB
        IntKnob("spark.io.compression.zstd.level", 1, 9, default=1),
        IntKnob("spark.sql.codegen.maxFields", 50, 500, default=100),
        BoolKnob("spark.sql.codegen.wholeStage", default=True),
        IntKnob("spark.sql.sources.parallelPartitionDiscovery.threshold", 8, 128, default=32),
        IntKnob("spark.sql.statistics.histogram.numBins", 64, 1024, default=254),
        BoolKnob("spark.sql.join.preferSortMergeJoin", default=True),
        IntKnob("spark.sql.limit.scaleUpFactor", 2, 16, default=4),
        IntKnob("spark.sql.shuffle.sortBeforeRepartition", 0, 1, default=1),
        FloatKnob("spark.scheduler.listenerbus.eventqueue.capacity", 1000, 100000, log=True, default=10000),
        IntKnob("spark.broadcast.compress", 0, 1, default=1),
        IntKnob("spark.checkpoint.compress", 0, 1, default=0),
        IntKnob("spark.files.maxPartitionBytes", 16, 512, default=128),
        IntKnob("spark.files.openCostInBytes", 1, 64, default=4),                # MB
        FloatKnob("spark.sql.cbo.joinReorder.card.weight", 0.0, 1.0, default=0.7),
        BoolKnob("spark.sql.cbo.enabled", default=False),
    ]
    assert len(knobs) == 60, f"expected 60 knobs, got {len(knobs)}"
    return ConfigSpace(knobs)


# the knobs ``spark35_space`` adds to ``spark_space``: each with its name,
# default and unit as Spark 3.5 documents them (sizes in the unit of the
# comment, times in seconds unless marked), and a range a tuner would search
_SPARK35_ADDED = [
    # ---- configuration.html, Shuffle Behavior
    IntKnob("spark.reducer.maxReqsInFlight", 1, 2147483647, log=True, default=2147483647),
    IntKnob("spark.reducer.maxBlocksInFlightPerAddress", 1, 2147483647, log=True, default=2147483647),
    IntKnob("spark.network.maxRemoteBlockSizeFetchToMem", 16, 2048, log=True, default=200),   # MB
    IntKnob("spark.shuffle.unsafe.file.output.buffer", 16, 1024, log=True, default=32),       # KB
    IntKnob("spark.shuffle.spill.diskWriteBufferSize", 256, 8192, log=True, default=1024),    # KB
    IntKnob("spark.shuffle.io.maxRetries", 0, 10, default=3),
    IntKnob("spark.shuffle.io.retryWait", 1, 30, default=5),
    BoolKnob("spark.shuffle.io.preferDirectBufs", default=True),
    IntKnob("spark.shuffle.service.index.cache.size", 16, 1024, log=True, default=100),       # MB
    IntKnob("spark.shuffle.accurateBlockThreshold", 10, 1000, log=True, default=100),         # MB
    BoolKnob("spark.shuffle.reduceLocality.enabled", default=True),
    IntKnob("spark.shuffle.mapOutput.minSizeForBroadcast", 64, 8192, log=True, default=512),  # KB
    BoolKnob("spark.shuffle.detectCorrupt", default=True),
    BoolKnob("spark.shuffle.detectCorrupt.useExtraMemory", default=False),
    BoolKnob("spark.shuffle.readHostLocalDisk", default=True),
    BoolKnob("spark.shuffle.checksum.enabled", default=True),
    CatKnob("spark.shuffle.mapStatus.compression.codec", ("lz4", "lzf", "snappy", "zstd"), default="zstd"),
    # ---- configuration.html, Compression and Serialization
    IntKnob("spark.io.compression.snappy.blockSize", 8, 128, log=True, default=32),           # KB
    IntKnob("spark.io.compression.zstd.bufferSize", 8, 256, log=True, default=32),            # KB
    BoolKnob("spark.kryo.referenceTracking", default=True),
    BoolKnob("spark.kryo.unsafe", default=False),
    IntKnob("spark.kryoserializer.buffer", 16, 1024, log=True, default=64),                   # KB
    IntKnob("spark.serializer.objectStreamReset", 10, 1000, log=True, default=100),
    # ---- configuration.html, Memory Management
    IntKnob("spark.storage.unrollMemoryThreshold", 256, 16384, log=True, default=1024),       # KB
    BoolKnob("spark.storage.replication.proactive", default=False),
    BoolKnob("spark.cleaner.referenceTracking", default=True),
    BoolKnob("spark.cleaner.referenceTracking.blocking", default=True),
    BoolKnob("spark.cleaner.referenceTracking.blocking.shuffle", default=False),
    # ---- configuration.html, Execution Behavior
    BoolKnob("spark.broadcast.checksum", default=True),
    IntKnob("spark.executor.heartbeatInterval", 5, 60, default=10),
    IntKnob("spark.files.fetchTimeout", 30, 300, default=60),
    BoolKnob("spark.files.useFetchCache", default=True),
    # ---- configuration.html, Scheduling
    IntKnob("spark.locality.wait.process", 0, 10, default=3),
    IntKnob("spark.scheduler.maxRegisteredResourcesWaitingTime", 1, 120, default=30),
    CatKnob("spark.scheduler.mode", ("FIFO", "FAIR"), default="FIFO"),
    IntKnob("spark.speculation.interval", 10, 1000, log=True, default=100),                   # ms
    IntKnob("spark.speculation.minTaskRuntime", 10, 10000, log=True, default=100),            # ms
    IntKnob("spark.task.cpus", 1, 4, default=1),
    # ---- configuration.html, Dynamic Allocation (maxExecutors: infinity,
    # which Spark holds as Int.MaxValue)
    BoolKnob("spark.dynamicAllocation.enabled", default=False),
    IntKnob("spark.dynamicAllocation.minExecutors", 0, 24, default=0),
    IntKnob("spark.dynamicAllocation.maxExecutors", 2, 2147483647, log=True, default=2147483647),
    IntKnob("spark.dynamicAllocation.executorIdleTimeout", 10, 600, log=True, default=60),
    IntKnob("spark.dynamicAllocation.schedulerBacklogTimeout", 1, 30, default=1),
    FloatKnob("spark.dynamicAllocation.executorAllocationRatio", 0.1, 1.0, default=1.0),
    # ---- sql-performance-tuning.html, Adaptive Query Execution
    IntKnob("spark.sql.adaptive.advisoryPartitionSizeInBytes", 16, 1024, log=True, default=64),   # MB
    IntKnob("spark.sql.adaptive.coalescePartitions.minPartitionSize", 1, 64, log=True, default=1),  # MB
    BoolKnob("spark.sql.adaptive.coalescePartitions.parallelismFirst", default=True),
    BoolKnob("spark.sql.adaptive.localShuffleReader.enabled", default=True),
    IntKnob("spark.sql.adaptive.maxShuffledHashJoinLocalMapThreshold", 0, 512, default=0),    # MB
    FloatKnob("spark.sql.adaptive.skewJoin.skewedPartitionFactor", 1.5, 20.0, log=True, default=5.0),
    IntKnob("spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes", 32, 2048, log=True, default=256),  # MB
    BoolKnob("spark.sql.adaptive.forceOptimizeSkewedJoin", default=False),
    BoolKnob("spark.sql.adaptive.optimizeSkewsInRebalancePartitions.enabled", default=True),
    FloatKnob("spark.sql.adaptive.rebalancePartitionsSmallPartitionFactor", 0.05, 0.5, default=0.2),
    # ---- configuration.html, Runtime SQL Configuration: optimizer
    BoolKnob("spark.sql.optimizer.dynamicPartitionPruning.enabled", default=True),
    BoolKnob("spark.sql.optimizer.runtime.bloomFilter.enabled", default=True),
    IntKnob("spark.sql.optimizer.runtime.bloomFilter.creationSideThreshold", 1, 320, log=True, default=10),  # MB
    BoolKnob("spark.sql.cbo.joinReorder.enabled", default=False),
    IntKnob("spark.sql.cbo.joinReorder.dp.threshold", 4, 24, default=12),
    BoolKnob("spark.sql.cbo.starSchemaDetection", default=False),
    BoolKnob("spark.sql.statistics.histogram.enabled", default=False),
    BoolKnob("spark.sql.bucketing.coalesceBucketsInJoin.enabled", default=False),
    # ---- sql-performance-tuning.html and Runtime SQL Configuration: file sources
    IntKnob("spark.sql.files.openCostInBytes", 1, 64, log=True, default=4),                   # MB
    BoolKnob("spark.sql.parquet.enableVectorizedReader", default=True),
    BoolKnob("spark.sql.parquet.filterPushdown", default=True),
    IntKnob("spark.sql.parquet.columnarReaderBatchSize", 512, 32768, log=True, default=4096),
    BoolKnob("spark.sql.inMemoryColumnarStorage.enableVectorizedReader", default=True),
    IntKnob("spark.sql.sources.parallelPartitionDiscovery.parallelism", 100, 100000, log=True, default=10000),
]

# the added knobs the cost model reads (``model._added_factors``): first-
# order effects, then the second-order knobs that shape them; every other
# added knob is long tail
SPARK35_INFLUENTIAL_KNOBS = [
    "spark.sql.parquet.enableVectorizedReader",
    "spark.sql.parquet.filterPushdown",
    "spark.sql.files.openCostInBytes",
    "spark.sql.optimizer.dynamicPartitionPruning.enabled",
    "spark.sql.optimizer.runtime.bloomFilter.enabled",
    "spark.sql.adaptive.advisoryPartitionSizeInBytes",
    "spark.sql.adaptive.coalescePartitions.minPartitionSize",
    "spark.sql.adaptive.coalescePartitions.parallelismFirst",
    "spark.sql.adaptive.localShuffleReader.enabled",
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor",
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes",
    "spark.dynamicAllocation.enabled",
    "spark.dynamicAllocation.minExecutors",
    "spark.dynamicAllocation.maxExecutors",
    "spark.reducer.maxReqsInFlight",
    "spark.sql.optimizer.runtime.bloomFilter.creationSideThreshold",
    "spark.dynamicAllocation.executorIdleTimeout",
    "spark.dynamicAllocation.schedulerBacklogTimeout",
    "spark.dynamicAllocation.executorAllocationRatio",
]

# the documented default of every added knob: the cost model reads an
# added knob that is absent, or at this value, as having no effect at all
SPARK35_DEFAULTS = {k.name: k.default_value() for k in _SPARK35_ADDED}


def spark35_space() -> ConfigSpace:
    knobs = spark_space().knobs + list(_SPARK35_ADDED)
    assert len(knobs) == 128, f"expected 128 knobs, got {len(knobs)}"
    return ConfigSpace(knobs)
