from repro.core.bitmap_alloc import (PAGES_PER_BLOCK, USABLE_PER_BLOCK,
                                     BitmapPageAllocator)
from repro.core.governor import (GovernorAction, GovernorConfig,
                                 MemoryGovernor)
from repro.core.hibernate import DeflateStats, HibernationManager, WakeStats
from repro.core.instance import EMBED_BLOCK, ModelInstance, WeightUnit
from repro.core.manager import (InstanceManager, ManagerConfig,
                                SharedWeightsRegistry)
from repro.core.metrics import MemoryReport, memory_report, per_rung_report
from repro.core.pool import PagePool
from repro.core.reap import ReapRecorder
from repro.core.state import (DEFLATED_STATES, PAUSED_STATES, RUNG_OF,
                              SERVABLE_STATES, TRANSITIONS, ContainerState,
                              Event, InvalidTransition, Rung, StateMachine)
from repro.core.store import StoreClient, StorePolicy, SwapStore
from repro.core.swap import ReapFile, SwapFile, WriteReceipt
