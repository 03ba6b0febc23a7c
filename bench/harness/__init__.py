"""The serving benchmark's harness: traffic, the engine's recorder, the
measured window, the trace reduction, the plain reference and the check.
Nothing here imports the program at module level: ``cell.run`` and the
engine subclass reach it through ``repro`` once JAX is up."""
