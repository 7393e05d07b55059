from .context import set_mesh, get_mesh
