"""Operations and bytes of the program's kernels and of a whole model step,
computed from shapes: the work the algorithm needs, not what the kernel
happens to touch. Each function counts one layer unless it says otherwise."""
