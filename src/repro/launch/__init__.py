"""Launch layer: device meshes (:mod:`repro.launch.mesh`) and the
multi-process gang launcher and supervisor
(:mod:`repro.launch.multihost`)."""
