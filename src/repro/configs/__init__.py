"""The paper's own experiment configs (``diffusion3d``: Fig. 1 / Fig. 2
3-D heat diffusion)."""
