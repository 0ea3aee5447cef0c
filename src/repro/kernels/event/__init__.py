from repro.kernels.event.ops import (EventTables, GroupTables,
                                     build_event_tables, event_push)
from repro.kernels.event.ref import event_push_ref

__all__ = ["EventTables", "GroupTables", "build_event_tables", "event_push",
           "event_push_ref"]
