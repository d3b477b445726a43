from cloud_server_tpu.inference.sampling import sample_logits  # noqa: F401
from cloud_server_tpu.inference.engine import (  # noqa: F401
    KVCache, encode, generate, init_cache, prefill)
from cloud_server_tpu.inference.beam import beam_search  # noqa: F401
from cloud_server_tpu.inference.request import (  # noqa: F401
    QueueFullError, Request)
from cloud_server_tpu.inference.router import ReplicatedRouter  # noqa: F401
from cloud_server_tpu.inference.http_server import HttpFrontend  # noqa: F401
