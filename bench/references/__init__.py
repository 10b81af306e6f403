"""Plain references, one module per architecture family, found by the name
a configuration file gives under ``reference``.  A reference imports
nothing of the program under test."""
