"""The yardstick: operations and bytes each kernel and each step needs, from shapes alone,
and the table of peaks (``peaks.json``). One module per kernel."""
