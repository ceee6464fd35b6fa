"""The chip benchmark's harness: finding a cell's parts by name, the traffic
generator, the operation counts, the peak table, host spans, the trace
reduction and one module per kind of cell (``train``, ``serve``)."""
