from repro.serving.batcher import ContinuousBatcher, Request, stack_tokens
from repro.serving.engine import StageServer
from repro.serving.arrivals import (ArrivalProcess, PoissonArrivals,
                                    TraceArrivals, BurstyArrivals,
                                    RampArrivals, make_arrivals,
                                    arrivals_from_dict, SCENARIOS)
from repro.serving.telemetry import Telemetry, percentile
from repro.serving.runtime import (ServingRuntime, RuntimeStage, EventLoop,
                                   COLD_START_SECONDS)
from repro.serving.fleet import (FleetRuntime, FleetTenant, build_fleet,
                                 scale_topology)
