from hivemind_tpu.utils.asyncio_utils import (
    achain,
    aenumerate,
    aiter_with_timeout,
    amap_in_executor,
    anext_safe,
    as_aiter,
    attach_event_on_finished,
    azip,
    cancel_and_wait,
    enter_asynchronously,
    spawn,
    switch_to_uvloop,
)
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.loop import LoopRunner, get_loop_runner
from hivemind_tpu.utils.nested import (
    nested_compare,
    nested_flatten,
    nested_map,
    nested_pack,
)
from hivemind_tpu.utils.performance_ema import PerformanceEMA
from hivemind_tpu.utils.profiling import device_memory_stats
from hivemind_tpu.utils.serializer import MSGPackSerializer, SerializerBase
from hivemind_tpu.utils.streaming import combine_from_streaming, split_for_streaming
from hivemind_tpu.utils.tensor_descr import BatchTensorDescriptor, TensorDescriptor
from hivemind_tpu.utils.timed_storage import (
    MAX_DHT_TIME_DISCREPANCY_SECONDS,
    DHTExpiration,
    TimedStorage,
    ValueWithExpiration,
    get_dht_time,
)
