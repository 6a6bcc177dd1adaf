"""The benchmark's own library: cell discovery, the drivers of each kind of
traffic, the trace reduction, the plain references and the table of peaks.
Nothing here is imported by the program; the program is imported from
``src/`` only as the system under test."""
