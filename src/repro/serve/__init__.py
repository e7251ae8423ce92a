from repro.serve.durability import (DurableQoSEngine, FaultInjection,
                                    pack_engine, serving_digest, unpack_into)
from repro.serve.engine import (Request, ServeEngine, make_prefill_step,
                                make_serve_step)
from repro.serve.qos import QoSConfig, QoSPlacementEngine, RouteRequest
