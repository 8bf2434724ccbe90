"""Mirror / pack: the pod table's slots in use (caps.pods less the mirror's free slots) at their fullest, of the window's close and the end of the grace drain, over its capacity; 1.0 is a CapacityError and a _grow inside the run."""

from benchmark import readers


def read(obs):
    return readers.pod_table_fill(obs)
