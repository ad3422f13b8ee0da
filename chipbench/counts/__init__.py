"""Operations and bytes a step or kernel needs, computed from its shapes.

These set the least time a roofline share divides by; they count the
work the algorithm needs, never the sizes of buffers it happens to use.
"""
