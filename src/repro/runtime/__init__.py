"""Runtime services: fault tolerance, elastic meshes, compile cache, tracing."""
