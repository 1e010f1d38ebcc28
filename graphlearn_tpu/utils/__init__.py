from .checkpoint import CheckpointManager
from .common import (count_dict, get_free_port, load_module,
                     merge_dict)
from .compat import shard_map
from .device import (enable_compilation_cache, ensure_device,
                     get_available_device, global_device_put)
from .exit_status import python_exit_status
from .faults import FaultError, fault_point
from .mixin import CastMixin
from .singleton import Singleton
from .strict import strict_enabled, strict_guards
from .tensor import convert_to_array, id2idx, squeeze_dict
from .topo import (coo_to_csc, coo_to_csr, csr_to_coo, csr_to_csc, ind2ptr,
                   ptr2ind)
from .trace import (DispatchCounter, count_dispatches, counter_get,
                    counter_inc, counters, device_op_ms,
                    device_program_ms, dispatch_snapshot, profile_trace,
                    record_dispatch, reset_counters, wrap_dispatch)
from .units import format_size, parse_size
