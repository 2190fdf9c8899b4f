"""Plain float32 references, one module per model family.  They import
nothing of the program: the sizes come from the configuration file and
the weights from ``chipbench.weights``."""
