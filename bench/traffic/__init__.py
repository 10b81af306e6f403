"""Traffic mixes: each ``<mix>.json`` here is data that ``generate`` reads."""
