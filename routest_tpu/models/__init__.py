from routest_tpu.models.eta_mlp import EtaMLP  # noqa: F401
from routest_tpu.models.gbdt import GBDT, from_xgboost_json  # noqa: F401
from routest_tpu.models.gnn import RoadGNN  # noqa: F401
from routest_tpu.models.route_transformer import RouteTransformer  # noqa: F401
from routest_tpu.models.route_lm import RouteLM  # noqa: F401
